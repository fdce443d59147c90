"""Quickstart: the paper's listing 1 — an intensity-inverting filter.

Follows the path of §III-C with the declarative operator-graph front-end
(docs/pipeline.md): declare the operator, bind its ports, run.  The
paper's imperative 11-step listing (set handles, init, launch) still
works — see the migration section of docs/pipeline.md — but new code
wires operators with ``bind()`` + ``Pipeline``.

Run:  PYTHONPATH=src python examples/quickstart.py [input.png] [output.png]
"""
import sys

import numpy as np

from repro.core import (CLapp, DeviceTraits, Pipeline, PlatformTraits,
                        ProfileParameters, SyncSource, XData,
                        enable_compile_cache)
from repro.processes import Negate
from repro.processes.negate import NegateParams


def main() -> None:
    in_path = sys.argv[1] if len(sys.argv) > 1 else None
    out_path = sys.argv[2] if len(sys.argv) > 2 else "output.png"
    enable_compile_cache()

    # Step 0: get a new OpenCLIPER-style app
    app = CLapp()
    # Step 1: initialize the computing device (traits select it)
    app.init(PlatformTraits(), DeviceTraits())
    # Step 2: load kernel module(s) — one call, indexed by name
    app.loadKernels("negate")

    # Step 3: load input data (file or synthetic "Cameraman" stand-in)
    if in_path:
        data_in = XData(in_path, dtype=np.float32)
        arr = data_in.get_ndarray(0).host
        if arr.dtype != np.float32:
            data_in.get_ndarray(0).set_host(arr.astype(np.float32) / 255.0)
    else:
        yy, xx = np.mgrid[0:256, 0:256]
        img = (np.sin(xx / 17.0) * np.cos(yy / 11.0) * 0.5 + 0.5).astype(np.float32)
        data_in = XData({"img": img})

    # Step 4: declare the operator graph.  Ports are validated and the
    # output is allocated from inferred specs — no handle plumbing, no
    # manual output Data.  The first run() AOT-compiles (the paper's
    # init); every further run() is a pure launch at ~zero overhead.
    pipe = Pipeline(app) | Negate(app).bind(params=NegateParams(use_pallas=False))

    # Step 5: run — repeatedly, against the one compiled executable
    prof = ProfileParameters(enable=True)
    data_out = pipe.run(data_in)
    for _ in range(10):
        data_out = pipe.run(data_in, profile=prof)
    print(f"mean launch time over 10 runs: {prof.mean() * 1e6:.1f} us")

    # Step 6: results are already synced to host (sync=True default); save
    data_out.save(out_path, SyncSource.HOST_ONLY)
    print(f"wrote {out_path}")

    # verify against the oracle
    got = data_out.get_ndarray(0).host
    want = 1.0 - data_in.get_ndarray(0).host
    np.testing.assert_allclose(got, want, rtol=1e-6)
    print("negate output verified against oracle")


if __name__ == "__main__":
    main()
