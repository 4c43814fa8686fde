"""The benchmark: see benchmark/README.md. Nothing of the program imports this."""
