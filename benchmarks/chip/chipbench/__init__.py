"""The chip benchmark's yardstick: cell discovery, traffic, weights, the
plain reference, trace reduction, peaks and operation counts.

Nothing here imports the program under test except ``training``,
``serving`` and ``program``, which call its entry points."""
