"""The server with its timed path broken underneath: a Count whose answer
is a multiple of three comes back one too high — an answer altered where
it is produced. tests/test_rehearsal.py starts this in the server's place
and has to see `correct` come out false."""

import os
import sys

sys.path.insert(0, os.getcwd())  # the harness starts its server from the repo

from pilosa_tpu import executor  # noqa: E402
from pilosa_tpu.cli.main import main  # noqa: E402

_count = executor.Executor._execute_count


def _altered(self, index, call, shards):
    n = _count(self, index, call, shards)
    return n + 1 if n % 3 == 0 else n


executor.Executor._execute_count = _altered

if __name__ == "__main__":
    sys.exit(main(["server", "--config", sys.argv[1]]))
