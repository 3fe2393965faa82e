"""Work that a step requires, counted from shapes: the yardstick for
utilization and roofline shares. Nothing here reads the program."""
