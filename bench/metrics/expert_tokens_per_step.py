"""Tokens a held expert takes per decode step where it takes any: the
window ticks' ``local_pairs`` (the program's ``moe.local_pairs``
increments: routed picks of live rows that land on held experts) over
the sum of their ``experts_touched`` (held (layer, expert) pairs with at
least one token), in tokens."""

import moe_steps


def read(ctx):
    ticks = moe_steps.ticks(ctx)
    touched = sum(t[4] for t in ticks)
    return sum(t[3] for t in ticks) / touched if touched else None
