"""Request-lifecycle tracing + engine flight recorder (ISSUE 11).

The serving path grew far past what one HTTP histogram can see: a request
crosses queue → (chunked) admission → decode blocks → preempt/swap/resume →
cluster span-transfer/reroute, and until now the only way to attribute a
stall was archaeology over logs (BENCH_r05 died as an rc=124 fifteen
minutes in). This package makes the lifecycle observable in four layers:

- `journal`  — a preallocated bounded ring buffer of typed events owned by
  the engine loop (append is lock-free from the loop thread, O(1), no
  Python-object allocation, no device sync). Cross-thread producers
  (submit, span export) stage into a small locked sidecar the loop drains.
- `trace`    — per-request span trees keyed by a request id that
  propagates as W3C `traceparent` from HTTP headers through GenRequest,
  cluster dispatch/reroute, federation proxying, and LAIKV span-transfer
  frames, so a disaggregated prefill→decode request is ONE trace.
- `timeline` — journal → Chrome trace-event JSON (Perfetto-loadable),
  served at `/debug/timeline`.
- `postmortem` — the flight recorder: on engine-loop death the last N
  journal events + an engine state snapshot dump to a JSON file whose path
  rides the `loop_dead` gauge labels and the manager log.

`scopes` is the one vocabulary of `jax.named_scope`s the engine's programs
are written under (compile-time metadata), so that a capture can be read by
program and scope.

`profile` is the DECLARED measurement point (the LOCALAI_PROFILE debug
path) and is deliberately excluded from the trace-safety lint targets,
exactly like the engine drainer thread. What a capture shows of the engine
— program and kernel names, `loop/<phase>` and `dispatch/<program>` spans —
is listed in docs/OBSERVABILITY.md.
"""

from localai_tpu.observe.journal import EventJournal  # noqa: F401
from localai_tpu.observe.trace import (  # noqa: F401
    STORE,
    RequestTrace,
    TraceStore,
    format_traceparent,
    new_traceparent,
    parse_traceparent,
)
