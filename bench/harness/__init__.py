"""Surface-independent parts of the benchmark: the cell table, the chip
check, the window clock, trace reduction, work counts and the output."""
