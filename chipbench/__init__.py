"""The on-chip benchmark: named cells of a configuration under a traffic mix.

``python -m chipbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once (see :mod:`chipbench.run`).  Every
configuration, traffic mix and per-layer metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it.

The system under test is the ``repro`` package under ``src/`` of the
checkout; it is put on the import path here so that the benchmark runs
from the checkout's root without any environment of its own.  The TPU
runtime's own log files (``/tmp/tpu_logs`` by default, a fixed path
outside the checkout) are turned off for the benchmark and its children.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
