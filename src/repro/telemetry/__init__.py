"""Unified telemetry: metrics registry + event bus, span tracing, and the
perturbation-cost ledger.

Quick tour::

    from repro.telemetry import Recorder, run_report, format_report

    rec = Recorder(out_dir="telemetry_out")        # events.jsonl streams
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=pol, fabric=FabricConfig(), mtbf={"host": 50.0},
        recorder=rec))
    state = loop.run(loop.init_state(), batches, 200)
    rec.ledger.set_rates(c, x0_err)                # price the faults
    print(format_report(run_report(rec)))
    rec.close()                                    # trace.json + metrics.json

The default everywhere is :data:`NULL_RECORDER` — its emit points are
no-ops and the hot path is unchanged. Host spans (``scar/...``) are on
either way: each writes a profiler annotation and books its seconds into
the training loop's per-step rollup; a Recorder also keeps their records.
See DESIGN.md "Observability".
"""
from repro.telemetry.ledger import LedgerEntry, PerturbationLedger
from repro.telemetry.recorder import (EVENT_SCHEMA, NULL_RECORDER, Counter,
                                      Gauge, Histogram, NullRecorder,
                                      Recorder, read_events_jsonl)
from repro.telemetry.report import format_report, run_report
from repro.telemetry.spans import SpanRecord, SpanTracer, span

__all__ = ["Recorder", "NullRecorder", "NULL_RECORDER", "Counter", "Gauge",
           "Histogram", "EVENT_SCHEMA", "read_events_jsonl",
           "PerturbationLedger", "LedgerEntry", "SpanTracer", "SpanRecord",
           "span", "run_report", "format_report"]
