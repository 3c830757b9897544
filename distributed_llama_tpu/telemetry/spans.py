"""The span-name registry (ISSUE 16).

Every string literal passed as a span name — ``tel.span("...")`` /
``trace_span("...")`` on the span path (profiler annotation + ring), or ``trace.span(ctx, "...")`` /
``ctx.add_span("...")`` on a request trace — must be registered here and
documented in docs/OBSERVABILITY.md's span-name table. The static
analyzer's TRC-001 rule (analysis/rules/registries.py) cross-checks every
call-site literal against this tuple exactly the way FLT-001 checks fault
sites against ``faults.SITES``: an unregistered name can't drift into the
trace surface unseen, and a registered-but-never-emitted name is flagged
as a dead entry. Keep this tuple, the call sites, and the doc table in
sync when adding spans.
"""

from __future__ import annotations

SPAN_NAMES = (
    # engine ring-tracer spans (PR 1, engine/engine.py + parallel/)
    "forward",
    "prefill",
    "prefill_dispatch",
    "device_sample",
    "first_token_fetch",
    "decode_chunk_dispatch",
    "decode_chunk_fetch",
    "spec_verify",
    "transfer_probe",
    # batched-scheduler ring-tracer spans (engine/batch.py)
    "batch_decode_chunk",
    "batch_decode_fetch",
    "spec_verify_chunk",
    "prefix_spill_reload",
    "prefix_spill_fetch",  # the arena's spiller thread (engine/spill.py)
    "prefix_publish",
    "prefix_restore",  # a one-chip hit's pages enqueued into its row (ISSUE 40)
    "state_snapshot",
    "state_restore",
    "window_tail_publish",
    "window_tail_restore",
    # the scheduler's own phases (ISSUE 23): what the host was doing
    # between device programs; tools read them off a capture's xplane
    "sched_build",
    "sched_post_dispatch",
    "sched_deliver",
    "sched_wait",
    "prefill_chunk_dispatch",
    # the completion ledger's watcher thread (ISSUE 41,
    # telemetry/device_ledger.py): one per observed device program, open
    # while the watcher waits for it
    "device_interval",
    # request-trace spans (ISSUE 16, telemetry/trace.py): the per-request
    # tree assembled by RequestTraceStore and served at /debug/trace/<id>
    "queue_wait",
    "placement",
    "prefill_chunk",
    "prefill_chunk_device",  # that piece's interval on the device (the ledger's)
    "decode_stream",
    "batch_decode_chunk_row",
    "spec_verify_row",
    "prefix_match",
    "sse_send",
)
