"""Mean time a ``ScoringService.step`` in the window spends resolving
thresholds and filling the padded batch from the queue (the program's
``serve.assemble`` span), in milliseconds."""
from bench import program


def read(ctx):
    return program.mean("serve.assemble", 1e-6)
