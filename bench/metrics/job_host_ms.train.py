"""Host time of one ``Engine.run`` outside the program's call, per job of
the window: the program's ``engine.prepare`` span (configuration, data,
trial keys, placement) plus its ``engine.publish`` span (the hand-off of
trial (0, 0) to the store), in milliseconds."""
from bench import program


def read(ctx):
    prepare, publish = program.stats("engine.prepare"), program.stats("engine.publish")
    if prepare is None:
        return None
    total = prepare["mean"] * prepare["count"]
    if publish is not None:
        total += publish["mean"] * publish["count"]
    return total / prepare["count"] / 1e6
