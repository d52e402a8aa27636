"""The chip benchmark's yardstick: cell discovery, traffic, weights, the
plain reference, trace reduction, peaks and operation counts.  What
depends on one architecture lives in its family module
(``families/<family>.py``), found by the name its configuration gives.

Nothing here imports the program under test except ``training`` and
``serving``, which call its entry points, ``harness``, which reads its
compile cache, compile count and counters, and ``trace_reduce``, which
reads its phase names (``repro.core.scopes``); a family module builds
the program's ``ModelConfig``."""
