"""A stub of InternLM2's plain reference, for the harness's own test that
a second architecture needs new files only: it gives the interface's
names and computes nothing."""


def _stub(*args, **kw):
    raise NotImplementedError("the second architecture's test reference "
                              "is a stub")


to_f32 = logits = loss_and_grad = served_gaps = train = _stub
