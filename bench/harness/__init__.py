"""The benchmark's harness: everything here is the yardstick, none of it
is the program. `run.py` puts this directory's parent on `sys.path`."""
