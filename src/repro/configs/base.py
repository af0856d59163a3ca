"""Architecture configuration system.

Every selectable architecture (``--arch <id>``) is an ``ArchConfig``. The ten
assigned architectures live in one file each under ``repro/configs``; the
paper's own CNN policy networks are ``paac_nips`` / ``paac_nature``.

Each config also exposes a ``reduced()`` variant (<=2 layers, d_model<=512,
<=4 experts) used by the per-arch CPU smoke tests, and the full variant is
exercised only through the multi-pod dry-run (ShapeDtypeStruct lowering, no
allocation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned, global — see system spec)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Pipeline (asynchronous actor/learner) config — repro.pipeline
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PipelineConfig:
    """Knobs for the asynchronous actor/learner pipeline (``repro.pipeline``).

    ``num_actors`` is the number of actor replicas feeding the learner
    (GA3C's n_actors sweep): a single env handed to ``PipelinedRL`` is split
    along the env axis into ``num_actors`` equal shards, or a list of envs
    gives each replica its own pool. ``queue_depth`` bounds the shared
    trajectory queue: depth d lets the actors collectively run at most d
    rollouts ahead (depth 1 is classic double buffering — rollout i+1 is
    collected while rollout i is consumed). ``rho_bar`` and ``c_bar`` are the
    V-trace clips (Espeholt et al. 2018) on the importance ratio
    ρ_t = π_learner(a|s)/π_behaviour(a|s): ρ̄ bounds each step's TD-error
    correction, c̄ bounds the product that propagates corrections backwards
    through the n-step targets — what keeps queues deeper than 2 unbiased.
    ``float("inf")`` for both disables the correction exactly (the
    synchronous PAAC update, bit-for-bit). ``lockstep`` forces the (single)
    actor to wait for the learner's latest params before each rollout —
    synchronous semantics through the pipelined code path (used by
    equivalence tests); it requires ``num_actors == 1``.

    ``rollout_plane`` selects the queue plane carrying trajectories from the
    actors to the learner:

    * ``"device"`` — ``DeviceTrajectoryRing``: payloads stay on the
      accelerator end to end and the learner step donates them (the fast
      path; JAX-native envs only),
    * ``"host"`` — ``TrajectoryQueue``: payloads are host numpy arrays in
      reusable staging buffers, uploaded when the learner dispatches (the
      only option for ``HostEnvPool``, whose rollouts are born on the host;
      for JAX-native envs it is the GA3C-style baseline the benchmarks
      compare against),
    * ``"mesh"`` — ``MeshTrajectoryRing``: the device plane scaled across a
      1-axis ``("data",)`` device mesh (see ``mesh_shape`` below); with
      ``mesh_shape=1`` it is the device ring routed through the mesh
      machinery on one device — the configuration the bitwise mesh=1
      lockstep test pins against the flat device plane,
    * ``"auto"`` (default) — mesh ring when ``mesh_shape > 1``, else device
      ring for JAX-native envs, host queue for ``HostEnvPool``.

    ``actor_backend`` selects where the actor replicas *execute*:

    * ``"thread"`` (default) — replicas are threads in this process. Right
      whenever env stepping releases the GIL (JAX-native envs, C/C++
      emulators behind thin bindings) — collection overlaps the learner's
      jitted update for free.
    * ``"process"`` — each replica is a worker subprocess owning a private
      env pool rebuilt from a picklable ``repro.envs.HostEnvSpec`` (live
      pools cannot cross the boundary). Rollouts ride
      ``multiprocessing.shared_memory`` staging sets into the parent's
      ``TrajectoryQueue`` and params broadcast back through a shared-memory
      ping-pong slot. This is the only backend that scales *GIL-holding*
      Python emulators (ALE-style wrappers, pure-Python simulators), whose
      env stepping serializes the thread plane no matter how many replicas
      run; it implies the host rollout plane. The workers act on the host
      CPU: each pins its JAX to the CPU platform before any array exists,
      so the parent learner is the only process that touches the
      accelerator (a chip belongs to one process at a time).

    ``mesh_shape`` scales the device plane across accelerators:
    ``mesh_shape=D > 1`` builds a 1-axis ``("data",)`` ``jax.sharding.Mesh``
    over the first ``D`` devices and partitions the env/batch axis of every
    rollout over it — one actor lane per device feeds a per-device sub-ring
    (``MeshTrajectoryRing``), the learner consumes a globally-sharded batch
    (one sub-rollout from *every* lane per update) and its gradients
    all-reduce across the data axis (Stooke & Abbeel 2018's multi-GPU
    synchronous regime). CPU CI exercises it via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    **Valid combinations** (the plane matrix — everything else raises
    ``ValueError`` here or in ``PipelinedRL``):

    ====================  ===============  ==========================
    actor_backend         rollout_plane    mesh_shape
    ====================  ===============  ==========================
    thread, JAX env       auto/mesh        1 or D (mesh plane)
    thread, JAX env       auto/device      1 only (flat device ring)
    thread, JAX env       host             1 only (GA3C baseline)
    thread, HostEnvPool   auto/host        1 only (host plane)
    process, HostEnvSpec  auto/host        1 only (host plane; a
                                           device plane would require
                                           rollouts born on-device)
    ====================  ===============  ==========================

    In particular: the process backend *forces* the host plane (its
    rollouts are born in worker shared memory), so a device/mesh plane or
    ``mesh_shape > 1`` with ``actor_backend="process"`` is a contradiction
    and raises immediately; ``mesh_shape > 1`` likewise rejects
    ``rollout_plane="host"`` (mesh payloads are device-resident by
    construction — a sharded rollout on the host queue would force a
    cross-device gather) and ``rollout_plane="device"`` (the flat
    single-device ring cannot carry more than one lane — say ``"mesh"`` or
    ``"auto"``). ``lockstep`` requires a single actor *stream*:
    ``num_actors == 1``, or the mesh plane (whose lanes are consumed in
    lockstep sets anyway — one sub-rollout per lane per update).

    **Replay plane** (``replay_plane=True``): the trajectory stream becomes a
    sampled ``ReplayRing`` instead of a FIFO ring — actors *never block* on
    the learner (a full ring evicts its oldest rollout), sampled slots are
    *retained* for reuse, and each learner update draws ``replay_batch``
    resident rollouts (uniformly, or TD-error-weighted with
    ``prioritized=True``). This is the off-policy plane: it drives
    ``DQNAgent`` (whose TD target needs no staleness correction) and
    off-policy PAAC/PPO (V-trace clips correct the sampled rollouts'
    staleness ≫ 1). Replay payloads are device-resident whole rollouts, so
    the plane requires JAX-native envs with ``actor_backend="thread"``,
    ``rollout_plane`` of ``"auto"``/``"device"`` and ``mesh_shape == 1``;
    ``prioritized``/``replay_capacity``/``replay_batch`` in turn require
    ``replay_plane=True`` (they have no FIFO meaning). ``replay_capacity``
    counts resident *rollouts* (each ``t_max × shard_envs`` transitions),
    ``replay_batch`` is rollouts sampled per update.

    **Fault tolerance** (``repro.pipeline.supervisor``; see
    ``docs/fault_tolerance.md``): ``elastic=True`` arms the
    ``ActorSupervisor`` — a dying actor replica no longer hard-aborts the
    stream. Under ``restart_budget`` respawns per actor *slot* (exponential
    backoff from ``restart_backoff_s``) the dead replica is respawned with
    a fresh ``(actor_id, seq)`` epoch and re-leased the current params;
    past the budget (or with ``restart_budget=0``) its remaining quota is
    reassigned to the surviving replicas and the run degrades to fewer
    actors instead of aborting. ``elastic=False`` (default) is the
    pre-supervisor fail-fast path, bit-for-bit: any replica death closes
    the stream and ``run()`` raises. The mesh plane stays fail-fast
    regardless (a lane's death leaves the sharded batch unassemblable), so
    ``elastic`` with the mesh plane is rejected here. ``lease_timeout_s``
    bounds how long the learner waits to reserve a ping-pong buffer before
    failing loudly — the error names the party still holding the lease.
    ``fault_plan`` (a ``repro.pipeline.faults.FaultPlan``) deterministically
    injects faults for tests/CI; ``checkpoint_dir``/``checkpoint_every``
    snapshot full pipeline state (params, opt state, RNG keys, per-actor
    seq/quota counters, ring tickets) every N learner iterations for
    ``PipelinedRL.restore()`` kill-and-resume.
    """

    queue_depth: int = 2
    rho_bar: float = 1.0
    c_bar: float = 1.0
    num_actors: int = 1
    lockstep: bool = False
    rollout_plane: str = "auto"  # "auto" | "device" | "host" | "mesh"
    actor_backend: str = "thread"  # "thread" | "process"
    mesh_shape: int = 1  # devices on the ("data",) rollout mesh
    # off-policy replay plane (sampled ReplayRing instead of the FIFO ring)
    replay_plane: bool = False
    replay_capacity: int = 64  # resident rollouts before FIFO eviction
    replay_batch: int = 1  # rollouts sampled per learner update
    prioritized: bool = False  # TD-error-weighted sampling (else uniform)
    # observability (repro.telemetry; see docs/observability.md). Span
    # recording itself is always on — it *is* the RunResult idle accounting;
    # these knobs control the exports and the observer threads:
    trace_path: str = ""  # "" -> no Chrome trace written at run end
    metrics_jsonl: str = ""  # "" -> no JSONL heartbeat stream
    heartbeat_s: float = 1.0  # heartbeat tick interval
    stall_timeout_s: float = 0.0  # 0 -> stall watchdog off
    # fault tolerance (repro.pipeline.supervisor; docs/fault_tolerance.md)
    elastic: bool = False  # False -> pre-supervisor fail-fast, bit-for-bit
    restart_budget: int = 1  # respawns per actor slot before degrading
    restart_backoff_s: float = 0.05  # base of the exponential respawn backoff
    lease_timeout_s: float = 60.0  # param-slot reserve/publish deadline
    # a repro.pipeline.faults.FaultPlan (typed loosely: configs must stay
    # importable without pulling the pipeline package in — and FaultPlan
    # imports nothing back, so the runtime isinstance check lives in
    # PipelinedRL, not here)
    fault_plan: Optional[object] = None
    checkpoint_dir: str = ""  # "" -> periodic checkpointing off
    checkpoint_every: int = 0  # learner iterations between snapshots (0=off)

    def __post_init__(self):
        if self.mesh_shape < 1:
            raise ValueError(f"mesh_shape must be >= 1, got {self.mesh_shape}")
        if self.heartbeat_s <= 0:
            raise ValueError(
                f"heartbeat_s must be > 0, got {self.heartbeat_s}")
        if self.stall_timeout_s < 0:
            raise ValueError(
                f"stall_timeout_s must be >= 0 (0 = off), got "
                f"{self.stall_timeout_s}")
        if self.mesh_shape > 1:
            if self.actor_backend == "process":
                raise ValueError(
                    "mesh_shape > 1 requires actor_backend='thread': process"
                    " rollouts are born in host shared memory and cannot ride"
                    " the device-resident mesh plane"
                )
            if self.rollout_plane in ("host", "device"):
                raise ValueError(
                    f"mesh_shape={self.mesh_shape} requires rollout_plane="
                    "'auto' or 'mesh': the host TrajectoryQueue cannot carry"
                    " a sharded rollout, and the flat single-device ring"
                    " cannot carry more than one lane"
                )
            if self.num_actors not in (1, self.mesh_shape):
                raise ValueError(
                    "the mesh plane runs exactly one actor lane per mesh"
                    f" device: num_actors must be 1 (auto) or mesh_shape"
                    f"={self.mesh_shape}, got {self.num_actors}"
                )
        if self.actor_backend == "process" and self.rollout_plane in (
                "device", "mesh"):
            raise ValueError(
                "actor_backend='process' forces the host rollout plane"
                " (worker rollouts are born in shared memory); rollout_plane"
                f"={self.rollout_plane!r} is a contradiction"
            )
        if self.replay_capacity < 1:
            raise ValueError(
                f"replay_capacity must be >= 1, got {self.replay_capacity}")
        if self.replay_batch < 1:
            raise ValueError(
                f"replay_batch must be >= 1, got {self.replay_batch}")
        if self.replay_plane:
            if self.actor_backend == "process":
                raise ValueError(
                    "replay_plane requires actor_backend='thread': replay"
                    " payloads are device-resident whole rollouts and cannot"
                    " ride the process backend's shared-memory staging"
                )
            if self.mesh_shape > 1 or self.rollout_plane == "mesh":
                raise ValueError(
                    "replay_plane does not compose with the mesh plane yet:"
                    " a sampled batch would have to draw one sub-rollout per"
                    " lane coherently; use mesh_shape=1"
                )
            if self.rollout_plane == "host":
                raise ValueError(
                    "replay_plane requires the device plane (rollout_plane"
                    " 'auto' or 'device'): the ReplayRing retains sampled"
                    " slots on the accelerator, which the host TrajectoryQueue"
                    " staging buffers cannot do"
                )
        elif self.prioritized:
            raise ValueError(
                "prioritized=True requires replay_plane=True: FIFO rings"
                " consume each rollout exactly once, so sampling priorities"
                " have no meaning there"
            )
        if self.restart_budget < 0:
            raise ValueError(
                f"restart_budget must be >= 0, got {self.restart_budget}")
        if self.restart_backoff_s < 0:
            raise ValueError(
                f"restart_backoff_s must be >= 0, got "
                f"{self.restart_backoff_s}")
        if self.lease_timeout_s <= 0:
            raise ValueError(
                f"lease_timeout_s must be > 0, got {self.lease_timeout_s}")
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0 (0 = off), got "
                f"{self.checkpoint_every}")
        if self.checkpoint_every > 0 and not self.checkpoint_dir:
            raise ValueError(
                "checkpoint_every > 0 requires checkpoint_dir: periodic"
                " snapshots need somewhere to land")
        if self.elastic and (self.mesh_shape > 1
                             or self.rollout_plane == "mesh"):
            raise ValueError(
                "elastic=True does not compose with the mesh plane: a dead"
                " lane leaves every subsequent sharded batch unassemblable,"
                " so the mesh plane stays fail-fast (see"
                " docs/fault_tolerance.md)"
            )


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    """Configuration for one policy/value backbone.

    The PAAC framework is model agnostic (paper §3): every architecture gets
    the two-headed output of paper §4 — a softmax policy head and a linear
    value head — attached by ``repro.models.heads``.
    """

    name: str = "unnamed"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio | cnn
    source: str = ""  # citation (hf:... / arXiv:...)

    # trunk
    num_layers: int = 2
    d_model: int = 256
    vocab_size: int = 1024

    # attention
    attention: str = "gqa"  # "gqa" | "mla" | "none"
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    # YaRN rotary scaling (DeepSeek-V2's ``rope_scaling`` of type "yarn");
    # yarn_factor 0 -> plain RoPE
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # MLA (DeepSeek-V2 / MiniCPM3 style multi-head latent attention)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_absorb: bool = False  # matmul-absorption decode path (perf variant)

    # feed-forward
    d_ff: int = 1024
    mlp: str = "swiglu"  # "swiglu" | "gelu" | "none"

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim (0 -> use d_ff)
    first_dense_layers: int = 0  # leading layers that use a dense FFN
    dense_d_ff: int = 0  # hidden dim of those dense layers
    router_aux_coef: float = 0.01
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 4096  # routing group (sequence chunk) length
    # expert parallelism's share: this chip holds experts
    # [experts_held_from, experts_held_from + experts_held) of the
    # num_experts the router scores, and computes their part of the layer
    # with no drops (models.moe.moe_held_forward); 0 -> the capacity path
    # over all num_experts
    experts_held: int = 0
    experts_held_from: int = 0
    norm_topk_prob: bool = True  # renormalise the top-k routing weights
    routed_scaling_factor: float = 1.0

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256

    # hybrid (Zamba2-style: shared attention block applied periodically)
    shared_attn_every: int = 0  # 0 -> no shared attention

    # encoder-decoder (Seamless-style)
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_seq_len: int = 1024  # stub front-end frames/patches

    # modality front-end stub
    modality: str = "text"  # text | audio | vision
    prefix_len: int = 0  # patch/frame embedding prefix length (vlm)
    frontend_dim: int = 0  # raw front-end embedding dim (0 -> d_model, no proj)

    # long-context variant
    sliding_window: int = 0  # 0 -> full causal attention
    supports_long_context: bool = False  # may run long_500k

    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    cache_dtype: str = ""  # "" -> compute_dtype
    norm_eps: float = 1e-5

    # heads / RL
    num_actions: int = 0  # 0 -> action space == vocab (token actions)
    tie_policy_head: bool = False

    # cnn (paper's arch_nips / arch_nature)
    cnn_spec: Tuple[Tuple[int, int, int], ...] = ()  # (features, kernel, stride)
    cnn_dense: int = 0
    obs_shape: Tuple[int, ...] = ()

    # remat policy for the scanned trunk: "none"|"full"|"dots"
    remat: str = "dots"
    # sequence-shard attention over "model" when heads don't divide the axis
    # ("auto"), or never ("off" — the pre-optimization baseline)
    attn_seq_shard: str = "auto"

    def actions(self) -> int:
        return self.num_actions if self.num_actions > 0 else self.vocab_size

    def expert_ff(self) -> int:
        return self.moe_d_ff if self.moe_d_ff else self.d_ff

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # -- reduced variant for CPU smoke tests ---------------------------------
    def reduced(self) -> "ArchConfig":
        """Same family, tiny: <=2 layers, d_model<=512, <=4 experts."""
        kw = dict(
            num_layers=min(self.num_layers, 2),
            d_model=min(self.d_model, 256),
            vocab_size=min(self.vocab_size, 512),
            num_heads=min(self.num_heads, 4),
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            head_dim=min(self.head_dim, 64) if self.head_dim else 0,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            param_dtype="float32",
            compute_dtype="float32",
            remat="none",
        )
        if self.attention == "mla":
            kw.update(
                q_lora_rank=min(self.q_lora_rank, 64) if self.q_lora_rank else 0,
                kv_lora_rank=min(self.kv_lora_rank, 32),
                qk_nope_dim=min(self.qk_nope_dim, 32),
                qk_rope_dim=min(self.qk_rope_dim, 16),
                v_head_dim=min(self.v_head_dim, 32),
            )
        if self.num_experts:
            kw.update(
                num_experts=min(self.num_experts, 4),
                num_experts_per_tok=min(self.num_experts_per_tok, 2),
                num_shared_experts=min(self.num_shared_experts, 1),
                moe_d_ff=min(self.expert_ff(), 128),
                first_dense_layers=min(self.first_dense_layers, 1),
                dense_d_ff=min(self.dense_d_ff, 256) if self.dense_d_ff else 0,
            )
            if self.experts_held:
                kw.update(experts_held=kw["num_experts"], experts_held_from=0)
        if self.ssm_state:
            kw.update(ssm_state=min(self.ssm_state, 16), ssm_chunk=32)
        if self.shared_attn_every:
            kw.update(shared_attn_every=2, num_layers=2)
        if self.is_encoder_decoder:
            kw.update(encoder_layers=min(self.encoder_layers, 2), encoder_seq_len=16)
        if self.prefix_len:
            kw.update(prefix_len=8)
        if self.frontend_dim:
            kw.update(frontend_dim=min(self.frontend_dim, 64))
        if self.sliding_window:
            kw.update(sliding_window=64)
        if self.family == "cnn":
            dense = min(self.cnn_dense, 64)
            kw.update(cnn_spec=self.cnn_spec[:2], cnn_dense=dense, d_model=dense)
        return self.replace(**kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], ArchConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ArchConfig]):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_config(name: str) -> ArchConfig:
    # import side-effect registration
    import repro.configs  # noqa: F401

    if name not in _REGISTRY:
        raise KeyError(f"unknown arch '{name}'; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs():
    import repro.configs  # noqa: F401

    return sorted(_REGISTRY)
