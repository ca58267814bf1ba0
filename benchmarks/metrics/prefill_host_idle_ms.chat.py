"""Engine: idle device milliseconds per prefill execution under the host
work of one admission: the own share of the program's
`scheduler.step.admit` span and its `scheduler.step.prefill.pack`,
`.dispatch` and `.readback` spans; each part is in the notes."""

from benchmarks.lib import spans


def read(run):
    return spans.prefill_host_idle_ms(run, "prefill_host_idle_ms_parts")
