"""Mean host time of a decode round's page bookkeeping over the window's
rounds: the program's ``backend.pages`` span (page extension, block-table
rows, copy-on-write, scratch rows), ms."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ("backend.pages",))
