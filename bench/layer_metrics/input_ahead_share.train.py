"""Share of the training step's input builds made ahead: the percentage
of the program's ``train.input`` spans starting in the traced window
whose ``ahead`` attribute is true, i.e. built by ``Trainer.run`` while
the step before ran on the device.  None where no such span carries the
attribute (a trainer that builds every input in place).  Moves
``train_samples_per_s``."""
from bench.harness.spans import window_spans


def read(ctx):
    spans = window_spans(ctx)
    if spans is None:
        return None
    tr = ctx.trace
    builds = [s.attrs for s in spans
              if s.name == "train.input" and tr.lo <= s.start_ns < tr.hi]
    if not any("ahead" in a for a in builds):
        return None
    return 100 * sum(a.get("ahead") is True for a in builds) / len(builds)
