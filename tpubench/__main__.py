import time

T0 = time.perf_counter()  # as near to the start of the process as Python lets us

import faulthandler  # noqa: E402
import sys  # noqa: E402

from tpubench.harness import main  # noqa: E402

WATCHDOG_S = 1150  # the first run of a cell in a checkout may take 1200 s

if __name__ == "__main__":
    # a hung run dumps every thread's stack and exits non-zero, with no result
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    sys.exit(main(t0=T0))
