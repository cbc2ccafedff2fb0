"""Mean host time of a decode round's dispatch over the window's rounds:
the program's ``backend.dispatch`` span (tokens, positions and block table
to the device, and the jitted step's enqueue), ms."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ("backend.dispatch",))
