"""Pipeline observability: spans, traces, heartbeats, stall watchdog.

The paper's whole argument is a timing argument (Fig. 2 decomposes where
wall-clock goes between acting, env stepping and learning); this package
is that decomposition made first-class for the asynchronous pipeline.
Every plane's hot path records bounded-ring monotonic-clock spans over a
fixed stage vocabulary (``CATEGORIES``: collect, queue.put_wait,
queue.get_wait, lease, publish, learner.update, shm.copy,
mesh.reassemble, the replay and fault stages, and the synchronous
driver's step.dispatch and metrics.read) into per-track
``SpanEmitter``s; a per-run ``Telemetry`` hub merges them into a Chrome
trace-event JSON (``--trace``, open in Perfetto), streams a JSONL
metrics heartbeat (``--metrics-jsonl``), and runs the stall watchdog
that names the stage each party is blocked in when progress stops.

The pre-existing ``RunResult`` idle accounting (``put_wait_s`` /
``get_wait_s`` / ``per_actor_idle_s``) is *derived from* these spans —
the emitters' per-category totals accumulate the exact float arithmetic
the old ad-hoc counters performed — so enabling telemetry changes no
reported number. While the JAX profiler collects, ``begin``/``end``
spans are mirrored into the profile as ``TraceAnnotation``s, on the same
clock as the device's ops. See ``docs/observability.md``.
"""
from repro.telemetry.hub import ShippedTrack, Telemetry
from repro.telemetry.spans import (
    CATEGORIES,
    COLLECT,
    FAULT_DETECT,
    FAULT_GIVEUP,
    FAULT_RESPAWN,
    LEASE,
    LEARNER_UPDATE,
    MESH_REASSEMBLE,
    METRICS_READ,
    PUBLISH,
    QUEUE_GET_WAIT,
    QUEUE_PUT_WAIT,
    REPLAY_ADD,
    REPLAY_EVICT,
    REPLAY_SAMPLE,
    SHM_COPY,
    STEP_DISPATCH,
    SpanEmitter,
    capture_enabled,
    set_capture,
    step_annotation,
)
from repro.telemetry.trace import write_chrome_trace

__all__ = [
    "CATEGORIES",
    "COLLECT",
    "QUEUE_PUT_WAIT",
    "QUEUE_GET_WAIT",
    "LEASE",
    "PUBLISH",
    "LEARNER_UPDATE",
    "SHM_COPY",
    "MESH_REASSEMBLE",
    "REPLAY_ADD",
    "REPLAY_SAMPLE",
    "REPLAY_EVICT",
    "FAULT_DETECT",
    "FAULT_RESPAWN",
    "FAULT_GIVEUP",
    "STEP_DISPATCH",
    "METRICS_READ",
    "SpanEmitter",
    "Telemetry",
    "ShippedTrack",
    "write_chrome_trace",
    "set_capture",
    "capture_enabled",
    "step_annotation",
]
