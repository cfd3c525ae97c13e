"""Not the benchmark. Every number comes from BENCHMARK.json's cells:

    python3 benchmark/run.py --workload <cell> ...

The name stays because tests/benchmark/test_manifest.py (:24, :101, :157)
uses `bench.py` as its example of a file outside the benchmark's paths;
the next `benchmark` PR points that test elsewhere and deletes this file
(ROADMAP D10).
"""
import sys

sys.exit(__doc__)
