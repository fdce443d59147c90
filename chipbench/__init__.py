"""On-chip benchmark of the OpenCLIPER reproduction: see run.py."""
