"""repro — RecoNIC-style RDMA compute offloading, reproduced on JAX."""
