"""Mean time a ``ScoringService.step`` in the window spends uploading the
batch, running the score program and downloading its errors and flags
(the program's ``serve.transfer`` span), in milliseconds."""
from bench import program


def read(ctx):
    return program.mean("serve.transfer", 1e-6)
