"""setup_s: process start to the window's opening commit edge: boot from
the world snapshot, compile (a cache hit after a checkout's first run),
traffic encoding, warm-up and the first cycle of the backlog. A
checkout's first run also provisions the world snapshot; that is timed
apart (`provision_s` on the `world:` line) and left out."""


def read(run):
    return run.setup_s
