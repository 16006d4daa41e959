"""Chip benchmark of the LSM store: YCSB traffic on the paper's
deployments, served through ``StorageGroup`` and read from the host
clock, the program's counters and the device trace.  See README.md."""
