"""Regenerate the paper's Tables 2/3 as machine-verified artifacts.

For every (format x op x rounding-mode): exhaustively validate the integer
expression + carry-in against the exact oracle, and print the table with
PASS / n/a entries — including the errata this reproduction discovered
(see DESIGN.md "Paper ambiguities").

Run:  PYTHONPATH=src python examples/paper_tables.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.caches import enable_compile_cache
from repro.core import CARRY_INS, lns_op_raw
from repro.core.formats import E4M3, E5M2
from repro.core.lns import LNS_CONSTS
from repro.core.rounding import MODES, Oracle

OPS = ("mul", "square", "div", "recip", "sqrt", "rsqrt")
COLS = MODES + ("faithful",)


def grids(op):
    if op in ("mul", "div"):
        X, Y = np.meshgrid(np.arange(256, dtype=np.uint8),
                           np.arange(256, dtype=np.uint8), indexing="ij")
        return X.ravel(), Y.ravel()
    return np.arange(256, dtype=np.uint8), None


def main():
    enable_compile_cache()
    for fmt in (E5M2, E4M3):
        oracle = Oracle(fmt)
        print(f"\nTABLE ({fmt.name.upper()}) — integer expression + carry-in, "
              f"exhaustively validated")
        print(f"{'op':8s} {'const':>6s} | " + " ".join(f"{m:>8s}" for m in COLS))
        print("-" * 80)
        for op in OPS:
            X, Y = grids(op)
            expected, valid = oracle.quantize_all(op, X, Y)
            cells = []
            for mode in COLS:
                spec = CARRY_INS[(fmt.name, op)][mode]
                if spec is None:
                    cells.append("—")
                    continue
                got = np.asarray(lns_op_raw(fmt, op, mode, X, Y))
                if mode == "faithful":
                    ok = (got == expected["rd"]) | (got == expected["ru"])
                else:
                    ok = got == expected[mode]
                bad = int((~ok & valid).sum())
                cells.append("PASS" if bad == 0 else f"FAIL{bad}")
            K = LNS_CONSTS[(fmt.name, op)]
            print(f"{op:8s} {K:#6x} | " + " ".join(f"{c:>8s}" for c in cells))

    print("""
Errata found by this validation (details in DESIGN.md):
  * E5M2 reciprocal constant: paper prints 0x88/0x87, correct is 0x78/0x77.
  * E5M2 reciprocal RU/RD carry-ins (eqs. 24/25) are swapped in the paper.
  * rsqrt shift order: (-X) >> 1 (arithmetic), not -(X >> 1); the printed
    "<<" in eqs. (28)/(49) is a typo for ">>".
  * E4M3 sqrt RN carry-in is x0+x1+x2+x3 (paper prints x3' for x3).
  * E4M3 sqrt RD/RZ carry-in (eq. 48) and the div/sqrt 'faithful = 0'
    entries require the corrections shown in carry_ins.py.
""")


if __name__ == "__main__":
    main()
