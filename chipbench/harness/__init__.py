"""The benchmark's harness: everything that measures, and nothing of the program."""
