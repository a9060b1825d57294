"""Padded rows over bucket rows (%) of the program's ``executor.pad``
spans in the traced slice: device work spent on rows no query sent.
Moves ``goodput_qps``."""
from chipbench import program_spans


def read(ctx):
    ss = program_spans.named(ctx, "executor.pad")
    if not ss:
        return None
    bucket = sum(s.attrs["bucket_rows"] for s in ss)
    return 100.0 * (bucket - sum(s.attrs["rows"] for s in ss)) / bucket
