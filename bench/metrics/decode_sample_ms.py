"""Mean host time from the end of a decode round's device step until the
host holds the round's tokens, over the window's rounds: the program's
``backend.fetch`` (logits to the host) and ``backend.argmax`` spans, ms."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ("backend.fetch", "backend.argmax"))
