"""The plain reference the benchmark compares every answer with.

A frozen copy of the simulator's semantics (`repro.core` as of the
benchmark's first version): topology, task trees, deques, victim selection,
link state, arrivals and the flight recorder, stepped one tick per loop
iteration. It imports nothing of the program and builds its own tables. It
keeps none of the program's speed machinery: no event leaping, no
famine-window replay, no staged deque backend, no Pallas kernel, no vmapped
or sharded sweep; every deque operation commits as its own scatter and the
grant export is plain jnp. Where a module docstring here still speaks of
the leap stepper, it describes the program's, which this copy does not
have. A change to the program that moves any simulated statistic shows up
as a difference against this copy.
"""
