"""Plain references and the generators the benchmark copies from the program,
so that a change to the program cannot move the yardstick."""
