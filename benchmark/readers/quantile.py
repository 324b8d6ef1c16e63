"""One quantile rule for every reader: the median for q = 0.5, otherwise the
nearest rank (the smallest value with at least q of the sample at or below
it), so a tail is a value that was measured."""
import math
import statistics


def quantile(values, q: float):
    if q == 0.5:
        return statistics.median(values)
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, math.ceil(q * len(ordered)) - 1)]
