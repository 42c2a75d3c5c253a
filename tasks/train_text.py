"""Text LLM training entry point.

Reference: ``tasks/train_text.py`` — parse config, construct trainer, train.
Usage: python tasks/train_text.py config.yaml --train.lr=1e-4 ...
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veomni_tpu.arguments import VeOmniArguments, parse_args, save_args
from veomni_tpu.trainer import TextTrainer


def main(argv=None):
    """Train from ``argv`` (default: the command line); returns the trainer
    so a caller can inspect the finished run."""
    from veomni_tpu.utils.xla_flags import apply_performance_flags

    apply_performance_flags()
    args = parse_args(VeOmniArguments, argv)
    save_args(args, args.train.output_dir)
    trainer = TextTrainer(args)
    trainer.train()
    return trainer


if __name__ == "__main__":
    main()
