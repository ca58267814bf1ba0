"""Executor: idle device milliseconds per train step at the epoch's
turn, under the program's `train.epoch_end.drain` (`block_until_ready`
of the parameters), `.losses` (one `float(loss)` per step of the epoch)
and `.reset` (the loader's reset) spans; each part is in the notes."""

from benchmarks.lib import spans


def read(run):
    return spans.train_phase_idle_ms(
        run,
        ("train.epoch_end.drain", "train.epoch_end.losses", "train.epoch_end.reset"),
        note="epoch_turn_idle_ms_parts",
    )
