"""Plain references the benchmark compares the program with.  They import
nothing of the program and take nothing it made."""
