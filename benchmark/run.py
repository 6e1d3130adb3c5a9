"""``python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one process, one cell, on the TPU it is started on."""
from __future__ import annotations

import sys

from .harness import main

if __name__ == "__main__":
    main(sys.argv[1:])
