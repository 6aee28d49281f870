"""Mean time a ``ScoringService.step`` in the window spends slicing the
batch's answers back into per-request results (the program's
``serve.complete`` span), in milliseconds."""
from bench import program


def read(ctx):
    return program.mean("serve.complete", 1e-6)
