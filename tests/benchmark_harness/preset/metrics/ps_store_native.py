"""ps_store_native: how many of the job's parameter servers run the
native embedding store (their logs say which backend loaded). A metric
added as a file of its own, read from an artefact of the run."""

import glob
import os
import re


def read(run):
    logs = glob.glob(os.path.join(run["out"], "ps*.log"))
    if not logs:
        return None
    native = 0
    for path in logs:
        with open(path, errors="replace") as f:
            if re.search(r"embedding store backend: native", f.read()):
                native += 1
    return float(native)
