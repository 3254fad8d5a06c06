"""Paper Table 1: processors, peak FLOP/s, STREAM bandwidth, ridge points.

Reproduces the paper's derived ridge points (Ivy-Bridge 5.2, Xeon Phi 6.4,
K40 7.4 F/B) and extends the table with the TPU v5e target (240 F/B bf16) —
the quantitative basis for claim C4: every application kernel (OI 0.4–2.2)
is memory-bound on every processor, and dramatically more so on TPU.
"""

from __future__ import annotations

from .common import PROCESSORS, ridge_point
from repro.launch.compile_cache import setup_compile_cache


def main(print_csv: bool = True):
    setup_compile_cache()
    rows = []
    for name, (peak, bw) in PROCESSORS.items():
        rp = ridge_point(name)
        rows.append((name, peak, bw, rp))
        if print_csv:
            print(f"table1_ridge/{name},0.0,"
                  f"peak_gflops={peak/1e9:.0f};stream_gbs={bw/1e9:.1f};"
                  f"ridge_fpb={rp:.1f}")
    # paper-published ridge values as a regression check
    assert abs(ridge_point("ivy-bridge") - 5.2) < 0.1
    assert abs(ridge_point("xeon-phi") - 6.4) < 0.1
    assert abs(ridge_point("k40") - 7.4) < 0.1
    return rows


if __name__ == "__main__":
    main()
