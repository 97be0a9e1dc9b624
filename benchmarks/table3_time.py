"""Paper Table 3: training time, DAEF vs the iterative AE.

The paper reports DAEF training 15-68x faster than the AE.  We measure both
on the same host (CPU here) over the dataset replicas and report the ratio.
AE epochs follow Table 5; DAEF uses 4 partitions like the paper's 4 cores.
"""
from __future__ import annotations

from benchmarks import table2_f1


def main(datasets=None, folds: int = 2) -> list[str]:
    lines = ["dataset,daef_s,ae_s,speedup"]
    for name in datasets or table2_f1.DAEF_ARCH:
        res = table2_f1.run_dataset(
            name, folds=folds, inits=("xavier",), include_ae=True
        )
        daef_s = res["daef_xavier"][2]
        ae_s = res["ae"][2]
        lines.append(
            f"{name},{daef_s:.3f},{ae_s:.3f},{ae_s / max(daef_s, 1e-9):.1f}"
        )
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    print("\n".join(main()))
