"""The kernel-parity suite of scripts/smoke_kernels.py (run_verify: the
int8 linear kernel vs the dequant oracle, both tree kernels vs the XLA
gather references, the fused sampling tail) on whatever backend is
attached: on a TPU the kernels run on hardware.

Usage:  python scripts/check_int8_kernel.py [B] [maxp]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from scripts import smoke_kernels

    smoke_kernels.run_verify(*(int(a) for a in sys.argv[1:3]))


if __name__ == "__main__":
    main()
