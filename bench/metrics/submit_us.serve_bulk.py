"""Mean host time of one ``ScoringService.submit`` in the window (the
program's ``serve.submit`` span), in microseconds."""
from bench import program


def read(ctx):
    return program.mean("serve.submit", 1e-3)
