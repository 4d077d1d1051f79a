"""Share of the traced training window in which a collective runs on a chip
and no other operation does, as a mean over the cell's chips
(``trace.collective_times``): the exchange the step waits for.

Collectives are the ops whose HLO names ``trace.COLLECTIVE`` matches
(``all-reduce``, ``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, each also as ``-start`` and ``-done``).  A traced
granite_3_2b.train.pp2tp2 run on a four-chip v5e host held, on each chip's
``XLA Ops`` line, ``all-reduce.<n>`` (tp's sums, synchronous: about 800 a
step), ``all-gather.<n>`` and the stage ring's ``collective-permute-start``
and ``collective-permute-done`` (each with or without ``.<n>``); no
reduce-scatter or all-to-all.  The ``Async XLA Ops`` line (the transfers
behind ``-start``/``-done``, and ``copy-start``/``slice-start``) is not
read: a collective there overlaps compute by construction."""

import sys


def read(ctx):
    red = ctx.get("trace")
    if ctx["driver"] != "train" or red is None or not any(red.collective_s):
        return None
    exposed = sum(red.collective_exposed_s) / red.devices
    print(f"collective_exposed_share: collectives {red.collective_s!r} s, "
          f"exposed {red.collective_exposed_s!r} s a chip", file=sys.stderr)
    return 100.0 * exposed / red.window_s
