"""The chip benchmark's harness: inputs from a seed, the drivers of the
system under test, the plain reference, the check, and the trace
reduction.  Nothing here is imported by the program."""
