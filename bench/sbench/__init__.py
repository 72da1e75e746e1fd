"""The on-chip benchmark of the Stretto engine: harness, traffic generator,
trace reduction, operation counts and peak table. Everything the yardstick
needs lives here, apart from the program it measures."""
