"""``transformers``/``torch`` cost about 30 s of every start to find no checkpoint
on a sealed machine (PERF.md, PR 21). With both names blocked the program's own
fallback runs at once: random-init weights (replaced by the run's seeded ones)
and its hash tokenizer."""

import sys


def before_server(cfg, log):
    sys.modules["transformers"] = None
    sys.modules["torch"] = None
    log("hook no_checkpoint_imports: transformers and torch are blocked")
