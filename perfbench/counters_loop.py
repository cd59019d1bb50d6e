"""The looped model's counter, read from the program's registry
(``mxnet_tpu.telemetry.REGISTRY``) after the run:
``mxnet_loop_layer_passes_total{model, kind}``, which
``gluon.model_zoo.ouro`` grows where a step is traced by the forward passes
of a decoder layer that step will make: one for an application whose
activations are kept (``kind="kept"``), two for one made again in the
backward (``kind="made_again"``)."""


def passes_by_kind():
    """``{kind: passes}`` summed over the models; None where the program
    keeps no such counter."""
    from mxnet_tpu import telemetry
    out = {}
    for m in telemetry.REGISTRY.collect():
        if m.name == "mxnet_loop_layer_passes_total" and m.value:
            kind = dict(m.labels).get("kind")
            out[kind] = out.get(kind, 0.0) + float(m.value)
    return out or None


def layer_passes_per_step(applications):
    """Forward passes of a decoder layer in one step, first and again,
    where a step applies a layer ``applications`` times.  The counter grows
    once a TRACE of the step, and a process may trace it more than once:
    the traces are the applications it counted (a kept pass is one, two
    passes made again are one) over ``applications``."""
    passes = passes_by_kind()
    if passes is None:
        return None
    counted = passes.get("kept", 0.0) + passes.get("made_again", 0.0) / 2
    return applications * sum(passes.values()) / counted
