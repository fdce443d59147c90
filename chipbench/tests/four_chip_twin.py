"""The four-chip stream's twin at test size, on four forced CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 -m chipbench.tests.four_chip_twin <empty directory>

Builds the tiny benchmark root in the directory and runs
``tiny.stream-4chip`` (sharded over data=4) through the harness twice,
untraced and traced, printing each result as one JSON line.  The harness
test runs it in a process of its own, since the device count is fixed
when JAX starts.
"""
import json
import sys
from pathlib import Path

from chipbench.tests import tiny


def main(tmp: str) -> None:
    for owner, name, value in tiny.cpu_patches():
        setattr(owner, name, value)
    root = tiny.make_root(Path(tmp))
    for trace in (0, 1):
        res = tiny.run(root, "tiny.stream-4chip", trace=trace, seconds=2.0)
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
