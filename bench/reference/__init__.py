"""Plain references: the same semantics as the program, written here in
straightforward ``jax.numpy`` and NumPy.  They import nothing of the
program and take nothing it made.  Each contraction takes a ``mode``: the
reference's own precision, or a lower one for the control that every
comparison has to fail (see :mod:`bench.reference.precision`)."""
