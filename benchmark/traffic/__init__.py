"""Traffic generators, one per kind; a workload file names its own."""
