import sys
import time

START = time.time()  # set-up is counted from here

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(started=START))
