"""Test-only server host (benchmark/test_correct.py): the timed path
broken underneath. It alters one MatchOut record where the serve loop
produces it, then hosts the server exactly as `benchmark.host` does; a
run on it must come out `correct: false`."""

from __future__ import annotations

import sys

ALTER_RECORD = 5000     # the produce call whose value is altered


def main(argv=None) -> int:
    from kme_tpu.bridge.service import MatchService

    from benchmark import host

    produce_out = MatchService._produce_out
    calls = [0]

    def altered(self, key, value):
        calls[0] += 1
        if calls[0] == ALTER_RECORD:
            value = value.replace('"size":', '"size":1', 1)
        return produce_out(self, key, value)

    MatchService._produce_out = altered
    return host.main(argv)


if __name__ == "__main__":
    sys.exit(main())
