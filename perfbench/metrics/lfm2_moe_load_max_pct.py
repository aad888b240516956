"""100 x the tokens of the fullest held expert over the mean of the held
experts: ``moe_load_max_over_mean``'s reading (the counter's median over the
telemetry stretch, its spread on an earlier line) in a unit the accepted
tests allow; 100 is an even load."""

from .moe_load_max_over_mean import read as ratio


def read(ctx):
    value = ratio(ctx)
    return None if value is None else 100.0 * value
