"""End-to-end training driver example: train a reduced qwen3 for a few
hundred steps on CPU with checkpointing + crash-recovery demonstrated live.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""

import argparse
import tempfile

from repro.compile_cache import configure_compile_cache
from repro.launch.train import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="qwen3_8b")
    args = ap.parse_args()
    configure_compile_cache()

    with tempfile.TemporaryDirectory() as d:
        # phase 1: train, then simulate a crash at 60% of the run
        crash_at = max(args.steps * 3 // 5, 2)
        coord = build(args.arch, reduced=True, batch=4, seq=32,
                      steps=args.steps, ckpt_dir=d, lr=1e-3)
        try:
            coord.run(steps=args.steps, fail_at_step=crash_at)
        except RuntimeError as e:
            print(f"[simulated failure] {e}")

        # phase 2: a fresh coordinator restarts from the latest checkpoint
        coord2 = build(args.arch, reduced=True, batch=4, seq=32,
                       steps=args.steps, ckpt_dir=d, lr=1e-3)
        final_step, _ = coord2.run(steps=args.steps)

        log = coord.metrics_log + coord2.metrics_log
        print(f"\ntrained {args.arch} (reduced) to step {final_step}")
        print(f"loss: {log[0]['loss']:.4f} -> {log[-1]['loss']:.4f} "
              f"({'improved' if log[-1]['loss'] < log[0]['loss'] else 'NOT improved'})")
        print(f"resumed-from-checkpoint steps: {len(coord2.metrics_log)}")


if __name__ == "__main__":
    main()
