"""Set-up seconds of the build stage (``yardstick/setup_spans.py``)."""

from yardstick import setup_spans as SS


def read(red, ctx):
    spans = SS.setup(ctx)
    return None if spans is None else SS.build_s(spans)
