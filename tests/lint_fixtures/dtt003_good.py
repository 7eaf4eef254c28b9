"""DTT003 conforming fixture: a ``_train_*`` loop (as the device-resident
driver and each host-fed loop are) that wires the full contract."""


def _train_ok(FLAGS, ds, sv, logger, meter, stimer, eff, rmon, els):
    _log_recovery(sv, logger, 0, eff)  # noqa: F821 — parsed, not run
    for step in range(10):
        logger.scalars(step,
                       _display_scalars(meter, stimer, eff, rmon))  # noqa: F821
        els.maybe_resize(step)
