#!/usr/bin/env python3
"""The control of a cell's comparison: ``bench/run.py`` with the cell's
stated guarantee broken. The device keeps the window's writes in a
volatile cache that the kill throws away, so a save is not durable when
it returns and an fsync'd append does not survive a cold remount. Its
``correct`` has to come out false.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The benchmark's own runs never run it.
"""

import sys

import run

if __name__ == "__main__":
    sys.exit(run.main(control=True))
