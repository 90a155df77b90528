"""`entry.gen_lag_p95_ms` in a cell whose end-to-end metric is `out_tok_per_s`
(the open-loop `long-mixed`, PERF.md section 6, PR 40): that reader."""

from benchmark import harness

read = harness.load_module("layer_metrics", "entry.gen_lag_p95_ms").read
