"""The chip benchmark of the ds-array stack: ``python bench/run.py --help``."""
