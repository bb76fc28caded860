"""The repository's benchmark: cells from ``BENCHMARK.json``, run by ``python -m benchmark.run``.

Everything the numbers depend on lives here, where a PR that claims a gain
cannot change it: traffic generation, the seeded weights, the float32
references, the peak table, the needed-FLOP and needed-byte counts, and the
reduction from spans and device traces to metrics. From ``sparkdl_tpu`` the
benchmark takes only the entry points under test and what they emit.
See ``benchmark/README.md``.
"""
