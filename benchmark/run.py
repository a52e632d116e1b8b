#!/usr/bin/env python3
"""Entry point of the benchmark: see benchmark/lib/runner.py."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib.runner import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
