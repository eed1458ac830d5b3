"""Paper-faithful CNN reproduction (the paper's own setting, reduced scale).

    PYTHONPATH=src python examples/cnn_paper_repro.py            # pipeline run
    PYTHONPATH=src python examples/cnn_paper_repro.py --tables   # Tables 1+2

Default: the end-to-end pipeline on the paper CNN — train an FP teacher,
heuristic PTQ (calibrate + MMSE init), QFT recovery, int4 export — via
repro.pipeline (same path as ``python -m repro quantize --config paper_cnn``).
``--tables`` walks the paper's full Table 2 → Table 1 story with the exact
benchmark grid (paper/figures.py).
"""
import argparse

from repro.pipeline import PipelineConfig, run_pipeline


def run_tables():
    from paper.figures import table1_qft_vs_baselines, table2_no_qft
    print("— Table 2 (heuristics only, no QFT) —")
    for r in table2_no_qft():
        print(f"  {r['setting']:>22s}: acc {r['acc']:.3f} "
              f"(deg {r['deg']:+.3f})")
    print("\n— Table 1 (with QFT) —")
    for r in table1_qft_vs_baselines():
        extra = (f"  pre-QFT {r['acc_pre_qft']:.3f} -> recovered "
                 f"{r.get('recovered', 0):+.3f}" if "recovered" in r else "")
        print(f"  {r['setting']:>22s}: acc {r['acc']:.3f} "
              f"(deg {r['deg']:+.3f}){extra}")


def run_pipeline_demo(steps: int):
    pcfg = PipelineConfig(arch="paper-cnn", mode="w4a8", steps=steps,
                          teacher_steps=300, calib_samples=4096, cle=True,
                          base_lr=1e-3, log_every=max(steps // 4, 1))
    result = run_pipeline(pcfg, log=lambda s: print(f"  {s}"))
    ev = result.metrics["evaluate"]
    print(f"\nFP32 teacher accuracy:   {ev['acc_teacher']:.3f}")
    print(f"QFT student accuracy:    {ev['acc_student']:.3f}  "
          f"(deg {ev['acc_teacher'] - ev['acc_student']:+.3f})")
    print(f"deployed int4 accuracy:  {ev['acc_deployed']:.3f}  "
          f"(export parity max err {ev['export_parity_max_err']:.2g})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tables", action="store_true",
                    help="full Table 1/2 benchmark grid instead of the "
                         "pipeline demo")
    ap.add_argument("--steps", type=int, default=600)
    args = ap.parse_args()
    if args.tables:
        run_tables()
    else:
        run_pipeline_demo(args.steps)


if __name__ == "__main__":
    main()
