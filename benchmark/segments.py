"""The untraced window in segments: the window is ``SEGMENTS`` equal
runs of steps, each ended by the host reading the loss.

The run's ``train_tok_s_per_chip`` is ``total_rate``: all the window's
tokens over all its seconds, so that a stall inside the window — the
program's own (a save, a collection, a sync every few steps) or the
machine's — counts against it, as it counts against a user. Every
segment's rate and their median go on an earlier line of the run and
into its file: a stall of the machine lands in one segment and leaves
the median where it was, work the program does every few steps lands in
every segment, so the two readings side by side say which it was
(PERF.md, PR 25)."""

SEGMENTS = 5


def plan(seconds, step_s):
    """``(SEGMENTS, steps_per_segment)`` for a window of ``seconds`` at
    a step time of ``step_s`` (from the warm-up): the most whole steps
    that fit a segment, at least one."""
    if step_s <= 0:
        raise ValueError(f"step time {step_s!r} is not positive")
    return SEGMENTS, max(1, int(seconds / SEGMENTS / step_s))


def rates(segment_tokens, segment_seconds, chips):
    """Tokens per second per chip of each segment."""
    return [t / s / chips for t, s in zip(segment_tokens, segment_seconds)]


def total_rate(segment_tokens, segment_seconds, chips):
    """The run's ``train_tok_s_per_chip``: all tokens over all seconds
    and chips."""
    return sum(segment_tokens) / sum(segment_seconds) / chips
