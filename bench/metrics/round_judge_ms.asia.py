"""Mean self time of the engine round's ``judge`` span, as
``round_judge_ms.penguin`` reads it."""
from harness import plugins


def read(run):
    return plugins.load("metrics", "round_judge_ms.penguin").read(run)
