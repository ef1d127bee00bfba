"""Counts XLA compilations inside a block.

A copy of the listener in ``repro.analysis.compile_budget``: JAX's
dispatch layer logs one ``Finished XLA compilation of jit(<name>)``
line on the ``jax._src.dispatch`` logger for every backend compile or
persistent-cache load. A compile inside the measured window is set-up
leaking into the measurement.
"""
from __future__ import annotations

import collections
import contextlib
import logging
import re
from typing import Iterator

_COMPILE_RE = re.compile(r"Finished XLA compilation of (?P<name>\S+)")
_LOGGER_NAME = "jax._src.dispatch"


class _CompileCounter(logging.Handler):
    def __init__(self, counts: collections.Counter):
        super().__init__(level=logging.DEBUG)
        self.counts = counts

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if m:
            self.counts[m.group("name")] += 1


@contextlib.contextmanager
def compile_log() -> Iterator[collections.Counter]:
    """Count compilations by jitted-function name inside the block."""
    counts: collections.Counter = collections.Counter()
    handler = _CompileCounter(counts)
    logger = logging.getLogger(_LOGGER_NAME)
    old_level, old_prop = logger.level, logger.propagate
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    logger.propagate = False
    try:
        yield counts
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
        logger.propagate = old_prop
