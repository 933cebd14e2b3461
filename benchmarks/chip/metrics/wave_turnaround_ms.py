"""wave_turnaround_ms: host time from a wave's last token read to the
next wave's prefill dispatch, in ms, averaged over the waves that follow
another: ``finish_sequence`` of the old wave, then ``start_sequence`` and
the first walk of the new one.  A wait for the next arrival is not
counted."""


def read(ctx):
    waves = ctx.record.waves
    turns = [prev.finish_s + (w.prefill_t - w.admit_t)
             for prev, w in zip(waves, waves[1:])]
    return sum(turns) / len(turns) * 1e3 if turns else None
