"""`pytest benchmark/` runs here on the CPU: the server children of the
run tests come up under the Pallas interpreter (JAX_PLATFORMS=cpu), which
the harness admits only with its `allow_cpu` switch."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
