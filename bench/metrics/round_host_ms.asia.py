"""Mean, over the rounds in the traced part of the window, of the
engine's ``round`` span minus the device time of the round program it
enclosed: the host's share of a round."""
from harness import layers


def read(run):
    matched = layers.matched_rounds(run)
    if not matched:
        return None
    return sum(span - d for d, span, _, _ in matched) / len(matched) * 1e3
