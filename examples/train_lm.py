"""End-to-end training driver: a ~100M-parameter qwen2-family model for a
few hundred steps on the synthetic pipeline, with checkpointing and a
mid-run injected fault (recovers + replays deterministically).

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse

from repro.launch import compile_cache, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()
    compile_cache.enable()
    # ~100M params: reuse the trainer with a mid-size custom config by
    # training the mamba2-130m published config (129M params) end to end.
    history = train.main([
        "--arch", "mamba2-130m",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "256",
        "--ckpt-every", "100",
        "--inject-fault-at", str(args.steps // 2),
        "--lr", "1e-3",
    ])
    losses = [h["loss"] for h in history]
    print(f"loss: first 10 avg {sum(losses[:10]) / 10:.4f} -> "
          f"last 10 avg {sum(losses[-10:]) / 10:.4f}")
    assert losses[-1] < losses[0], "training did not reduce the loss"


if __name__ == "__main__":
    main()
