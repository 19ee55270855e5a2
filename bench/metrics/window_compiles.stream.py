"""Backend compiles (``jax.monitoring``) inside the traced window of a
stream cell; 0 where every program was warmed in set-up."""


def read(rd):
    return rd.compiles
