"""Programs set-up wrote to the persistent compile cache
(``yardstick/setup_spans.py``): 0 when every program was found there."""

from yardstick import setup_spans as SS


def read(red, ctx):
    spans = SS.setup(ctx)
    return None if spans is None else SS.cache_misses(spans)
