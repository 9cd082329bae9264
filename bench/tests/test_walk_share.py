"""The ``paged_attn_walk_share`` reader on the synthetic burst of
``test_spans``: pages walked over the full tables of every kernel call."""

import pytest

from bench import readers, spans
from bench.tests.test_spans import context, program_spans, scoped_ops


def test_walk_share_of_the_walked_pages():
    """Pages walked over the full tables of every kernel call: one call a
    mixed step, one a decode run's token (tables of 2 rows x 4 pages)."""
    ctx = context()
    walked = {0: 6, 1: 8, 2: 40}
    ctx.program = spans.Program(
        [s._replace(args={**s.args, "walked_pages": walked[s.args["it"]]})
         if s.name == "serve.iteration" else s for s in program_spans()],
        scoped_ops())
    share = readers.load_reader("paged_attn_walk_share").read(ctx)
    assert share == pytest.approx(100 * (6 + 8 + 40) / (2 * 4 * (1 + 1 + 16 // 2)))


def test_walk_share_reads_nothing_without_the_counter():
    """A program whose spans carry no ``walked_pages`` (the gather path,
    or a program before the counter) reports no walk share."""
    for ctx in (context(), context(with_spans=False)):
        assert readers.load_reader("paged_attn_walk_share").read(ctx) is None
