"""The on-chip benchmark's own code: harness, traffic loop, trace
reduction, roofline arithmetic and plain references.  It imports the
system under test only inside the adapters (``benchmarks/chip/adapters``)."""
