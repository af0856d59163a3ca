"""Serving launcher: lockstep batch demo, or continuous batching.

Two modes:

* **default (lockstep batch)** — the paper's master in isolation:
  batched prefill for ``--batch`` identical-length prompts, then a
  decode loop emitting one token per actor per step through
  ``serve_step``. Every actor starts and stops together.
* **``--continuous``** — the serving plane (``docs/serving.md``): an
  open-loop traffic source feeds a bounded admission queue; the
  ``Scheduler`` leases cache slots and requests join/leave the decode
  batch mid-flight. Reports aggregate tokens/s and p50/p99 request
  latency — the numbers ``benchmarks/serve_bench.py`` sweeps.

``--trace`` records phase spans (lockstep: ``prefill``/``decode``;
continuous: ``admit``/``prefill``/``decode``/``evict``) and writes a
Chrome trace-event JSON at exit. ``--metrics-jsonl`` streams the
heartbeat; in continuous mode it carries the ``serve_queue_depth`` and
``serve_active_slots`` gauges.

Examples:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --batch 8 --prompt-len 64 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --continuous --requests 16 --slots 4 --rate 8 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.steps import build_serve_step
from repro.models import init_policy
from repro.telemetry import Telemetry
from repro.utils import get_logger, use_compile_cache

log = get_logger("serve")

_PREFILL, _DECODE = 0, 1


def demo_streams(seed: int):
    """Split the demo's root key into its three independent streams.

    ``init_policy`` consumes its key in full; reusing the same key for
    the prompt draw (or the decode loop) would correlate weights with
    data. Split once at the top, hand each consumer its own stream, and
    never touch the root again.
    """
    root = jax.random.PRNGKey(seed)
    params_key, prompt_key, decode_key = jax.random.split(root, 3)
    return params_key, prompt_key, decode_key


def percentile_ms(xs, q: float) -> float:
    """Latency percentile in milliseconds (empty-safe for error-only runs)."""
    if not xs:
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), q) * 1e3)


def _run_lockstep_demo(args, cfg, params, hub, prompt_key, decode_key):
    em = hub.emitter("serve", categories=("prefill", "decode"))
    B, S = args.batch, args.prompt_len
    max_len = S + args.gen
    prompts = jax.random.randint(prompt_key, (B, S), 0, cfg.vocab_size)
    prefix = None
    if cfg.modality == "vision":
        prefix = jnp.ones((B, cfg.prefix_len, cfg.frontend_dim or cfg.d_model))
    if cfg.is_encoder_decoder:
        prefix = jnp.ones((B, cfg.encoder_seq_len,
                           cfg.frontend_dim or cfg.d_model))

    # prefill: cache sized for generation headroom
    t0 = time.perf_counter()
    from repro.models import policy_prefill

    em.begin(_PREFILL)
    try:
        logits, values, cache = jax.jit(
            lambda p, t: policy_prefill(p, cfg, t, prefix, max_len=max_len)
        )(params, prompts)
        jax.block_until_ready(logits)
    finally:
        em.end()
    t_prefill = time.perf_counter() - t0
    log.info("prefill %.3fs (%.0f tok/s)", t_prefill, B * S / t_prefill)

    serve_step = jax.jit(build_serve_step(cfg), donate_argnums=(1,))
    token = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    toks = [token]
    key = decode_key
    t0 = time.perf_counter()
    for i in range(args.gen):
        key, sub = jax.random.split(key)
        em.begin(_DECODE)
        try:
            token, value, cache = serve_step(
                params, cache, token, jnp.asarray(S + i, jnp.int32),
                jax.random.key_data(sub),
            )
        finally:
            em.end()
        toks.append(token)
    jax.block_until_ready(token)
    dt = time.perf_counter() - t0
    out = jnp.concatenate(toks, axis=1)
    log.info("decode %d tokens x %d actors: %.3fs (%.0f tok/s)",
             args.gen, B, dt, args.gen * B / dt)
    log.info("sample actor 0 tokens: %s", out[0, :16].tolist())


def _run_continuous(args, cfg, params, hub):
    from repro.pipeline.queue import TrajectoryQueue
    from repro.serving import DecodeEngine, OpenLoopTraffic, Scheduler

    max_len = args.prompt_len + args.gen
    engine = DecodeEngine(cfg, params, max_slots=args.slots, max_len=max_len)
    queue = TrajectoryQueue(depth=max(2, 2 * args.slots), telemetry=hub)
    sched = Scheduler(engine, queue, continuous=True, telemetry=hub)
    lo = max(1, args.prompt_len // 2)
    traffic = OpenLoopTraffic(
        queue, args.requests, seed=args.seed, rate_hz=args.rate,
        prompt_lens=(lo, args.prompt_len),
        gen_range=(max(1, args.gen // 2), args.gen), vocab=cfg.vocab_size)

    t0 = time.perf_counter()
    traffic.start()
    done = sched.run()
    traffic.join()
    wall = time.perf_counter() - t0

    ok = [r for r in done if r.status == "done"]
    lat = [r.latency_s for r in ok]
    total = sum(r.n_generated for r in ok)
    log.info("continuous: %d/%d requests done, %d tokens in %.3fs "
             "(%.1f tok/s aggregate, %d decode steps)",
             len(ok), len(done), total, wall, total / wall, sched.steps)
    log.info("latency p50 %.1f ms  p99 %.1f ms",
             percentile_ms(lat, 50), percentile_ms(lat, 99))
    for r in done:
        if r.status != "done":
            log.warning("request %d %s: %s", r.rid, r.status, r.error)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ASSIGNED_ARCHS, default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching service loop instead of the "
                    "lockstep batch demo")
    ap.add_argument("--requests", type=int, default=16,
                    help="[--continuous] total requests the traffic source "
                    "emits")
    ap.add_argument("--slots", type=int, default=4,
                    help="[--continuous] decode-batch width / cache slots")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="[--continuous] open-loop arrival rate in Hz "
                    "(0 = burst)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome trace-event JSON of serving spans "
                    "here (open in Perfetto)")
    ap.add_argument("--metrics-jsonl", default="",
                    help="append a JSONL metrics heartbeat here")
    args = ap.parse_args(argv)
    use_compile_cache()

    hub = Telemetry()
    if args.metrics_jsonl:
        hub.heartbeat_start(args.metrics_jsonl, interval=0.25)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params_key, prompt_key, decode_key = demo_streams(args.seed)
    params = init_policy(params_key, cfg)

    try:
        if args.continuous:
            _run_continuous(args, cfg, params, hub)
        else:
            _run_lockstep_demo(args, cfg, params, hub, prompt_key, decode_key)
    finally:
        hub.heartbeat_stop()
        if args.trace:
            hub.write_trace(args.trace)


if __name__ == "__main__":
    main()
