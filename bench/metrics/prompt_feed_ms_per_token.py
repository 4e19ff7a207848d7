"""Engine prompt feed: host time of the program's ``repro.prompt_feed``
spans (one per admitted request, feeding all of its prompt but the last
token) over the prompt tokens they fed (their ``tokens`` argument), in
the traced part of the window, in milliseconds per token. None where no
admission fell in the traced part."""

from harness import spans


def read(ctx):
    s = spans.of(ctx)
    feeds = s.named("repro.prompt_feed") if s else []
    tokens = sum(dict(e.args)["tokens"] for e in feeds)
    return 1e3 * sum(e.dur for e in feeds) / tokens if tokens else None
