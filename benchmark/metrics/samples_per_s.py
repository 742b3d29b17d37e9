"""samples_per_s: records trained per second, all chips together, over
the whole window: steps between the first and the last logged line
inside it x minibatch / the seconds between them (host clock: the
worker log's millisecond timestamps; lib/window.py)."""

from benchmark.lib import window


def read(run):
    return window.samples_per_second(run)
