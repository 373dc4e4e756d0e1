"""Entries: the call into the program that a cell's window drives, and the
comparison of what it produced with the plain reference.  Found by the
``entry`` key of a workload file.  Each has ``setup(cell) -> state``,
``check(cell, state, answers) -> {number: value}`` and
``control(cell, state, answers) -> answers``, which puts the reference at
the control's precision in the program's place; a closed-loop entry has
``call(state) -> answer``, an open-loop one ``start``, ``submit`` and
``stop``."""
