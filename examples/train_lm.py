"""Train a ~100M-parameter LM for a few hundred steps (loss must drop).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse

from repro.launch import compile_cache, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument(
        "--big", action="store_true",
        help="~130M-param configuration (use on a TPU/GPU host; the "
        "2-core CPU container default is an 8.7M reduced variant)",
    )
    args = ap.parse_args()
    if args.big:
        result = train.run(
            args.arch, steps=args.steps, batch=32, seq=1024,
            reduced=True, lr=3e-4, big=True,
        )
    else:
        result = train.run(
            args.arch, steps=args.steps, batch=8, seq=256, reduced=True, lr=6e-4
        )
    print(f"\narch={result['arch']} params={result['params'] / 1e6:.1f}M")
    print(f"loss {result['first_loss']:.3f} -> {result['final_loss']:.3f} "
          f"({'improved' if result['improved'] else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    compile_cache.enable()
    main()
