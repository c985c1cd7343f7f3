"""Benchmark machinery shared by every cell: the registry that finds a
cell's files by name, the traffic generator, the closed-loop driver,
the trace reduction, the work and peak tables, and the check of
`correct`."""
