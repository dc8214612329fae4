"""Device self time per served chunk of the ops under given phase
scopes (``tilt.*``), read from the cell's trace by
``bench/program_trace.py``; ``None`` when the trace holds no scopes."""
import program_trace


def phase_ms(ctx, *phases):
    data = program_trace.for_cell(ctx)
    if data is None or not ctx.chunks:
        return None
    by_phase = program_trace.phase_s(data)
    if by_phase is None:
        return None
    return 1e3 * sum(by_phase.get(p, 0.0) for p in phases) / len(ctx.chunks)
