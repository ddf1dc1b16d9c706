"""Benchmark harnesses.

The reference's only performance harness is ``go test -bench`` over a
seeded random instance (/root/reference/pkg/sat/bench_test.go:10-19,66-86)
and it publishes no numbers (SURVEY.md §6).  This package holds the
rebuild's measured equivalents:

  * :mod:`deppy_tpu.benchmarks.headline` — the driver-facing headline
    metric (batched catalog resolutions/sec, device vs serial host);
  * :mod:`deppy_tpu.benchmarks.suite` — all five BASELINE.json workload
    configs, host vs device.
"""
