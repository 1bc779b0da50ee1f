"""Tokens per second the clients received inside the window: for each
client, the token frames it received inside the window less one, over
the time from the first of them to the last; summed over the clients.

With every lane busy this is lanes over the mean gap between tokens.
Counting frames over the window's length instead would quantise: a
window holds a whole number of decode steps, and at 1.3 s a step (first
chip run of PR 22) 30 s hold 23 or 24 of them, a 4 % swing that says
nothing about the system.  A stream that straddles an edge of the window
counts the tokens that fell inside, and the time between a client's
streams (its next request, the prefill) is inside its span."""

from collections import defaultdict


def read(run):
    inside = defaultdict(list)          # client -> its stamps in the window
    for r in run.requests:
        inside[r.get("client", r["id"])].extend(
            s for s in r["stamps"] if run.t0 <= s < run.t1)
    rates = [(len(s) - 1) / (max(s) - min(s)) for s in inside.values()
             if len(s) > 1 and max(s) > min(s)]
    return sum(rates) if rates else None
