"""RETIRED in PR 58: this file reads nothing. `flash_attention_tile_share` was
a count from (T, blocks) that no run could move; `training/train.py
make_runtime` still prints it on its `flash attention:` line and keeps it as
the gauge `attn.score_tile_share`. The file is not deleted only because
docs/OBSERVABILITY.md names it and tests/test_docs.py holds every document to
files that exist, and a `benchmark` PR may edit neither (PERF.md section 7)."""


def read(run):
    return None
