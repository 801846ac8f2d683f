"""`python -m glom_tpu.train ...` — the training CLI's entry point
(train/cli.py; `-m glom_tpu.train.cli` works too)."""

import sys

if __name__ == "__main__":
    from glom_tpu.train.cli import main

    sys.exit(main())
