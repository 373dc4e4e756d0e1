"""Data makers, one per kind of configuration, found by the ``data`` key of
a configuration file.  Each makes the cell's inputs from its seed and lets
the plain reference make the same inputs again without the program."""
