"""Traffic generators: a mix's data file plus a seed in, rounds of calls
out. A mix (``benchmark/traffic/<mix>.json``) names its generator under
``generator`` (default ``rounds``), a file ``benchmark/generators/<name>.py``
that defines

- ``pairs(mix) -> [(op, size)]``: every (op, size) the mix can issue,
  which set-up warms up and gives buffers;
- ``rounds(mix, seed)``: endless rounds, each a list of calls, every
  call an ``(op, size)`` pair, issued back to back by one caller, or an
  ``(op, size, at)`` triple, issued no earlier than ``at`` seconds after
  the window opened and timed from then (open loop). The window closes
  at the first round boundary after its seconds.

The same seed gives the same calls. A later PR adds a mix as a data
file, and a new shape of traffic as a generator file beside these.
"""
from __future__ import annotations

from benchmark import load_file

DEFAULT = "rounds"


def load(mix: dict, root: str = None):
    """The generator module that ``mix`` names."""
    return load_file(root, "generators", mix.get("generator", DEFAULT))
