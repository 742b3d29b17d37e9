"""Notes when the machine did not schedule THIS process: a pause of
the whole sandbox, as the chip tool's machines make whenever a process
opens or closes the TPU (0.7-8.7 s) and about once in 48 s otherwise
(0.11 s; PERF.md Section 6, PR 29). A timed training loop reads such a
pause as one long ``device_wait``; a process of its own that only
sleeps tells a pause of the machine from a stall of the program.

    python scripts/pause_watch.py chiprun_out/pauses.ndjson &
    ... the timed job ...
    kill %1

Sleeps ``--period`` seconds in a loop and appends one JSON line,
``{"ts": <time.time() at wake-up>, "seconds": <since it went to
sleep>}``, for every sleep that lasted over ``--floor`` seconds. It
imports nothing of the program and touches no device.
"""

import argparse
import json
import time


def watch(path, period=0.01, floor=0.06, until=None):
    with open(path, "a", buffering=1) as out:
        last = time.perf_counter()
        while until is None or time.perf_counter() < until:
            time.sleep(period)
            now = time.perf_counter()
            if now - last > floor:
                out.write(json.dumps(
                    {"ts": time.time(), "seconds": now - last}) + "\n")
            last = now


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("path")
    parser.add_argument("--period", type=float, default=0.01)
    parser.add_argument("--floor", type=float, default=0.06)
    parser.add_argument("--seconds", type=float, default=None,
                        help="stop after this long (default: run until "
                        "killed)")
    args = parser.parse_args(argv)
    until = None
    if args.seconds is not None:
        until = time.perf_counter() + args.seconds
    try:
        watch(args.path, args.period, args.floor, until)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
