"""Traffic generation owned by the benchmark (never imports the program)."""
