"""DTT003 violating fixture: a ``_train_*`` loop that forgets the scalar
contract and the elastic poll."""


def _train_broken(FLAGS, ds, sv, logger, meter):
    for step in range(10):
        logger.scalars(step, {"images_per_sec": meter.images_per_sec})
