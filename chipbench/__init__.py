"""The on-chip benchmark of the RDMA offload engine (see ``run.py``)."""
