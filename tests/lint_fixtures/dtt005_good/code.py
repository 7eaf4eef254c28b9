"""DTT005 conforming fixture: literal, conditional-variable,
field-carried and parameterized span names, all in the table."""


def run(step, zb, point, tracer):
    with trace_span("good_span", step=step):  # noqa: F821
        pass
    name = "cond_a" if zb else "cond_b"
    with trace_span(name, step=step):  # noqa: F821
        pass
    tracer.record_instant(f"fault:{point}", step=step)


def drive(layout, step):
    with trace_span(layout.span, step=step):  # noqa: F821
        pass


def choose(zb, step):
    drive(Layout(span="field_a" if zb else "field_b"), step)  # noqa: F821
