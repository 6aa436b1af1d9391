"""``im2col_us.<cells>``: device microseconds per image spent in patch
extraction (``cnn.im2col``: each conv layer's im2col and the flatten
before the fc layers), over every layer.  Read as
``operand_prep_us`` is (``bench/scopes.py``), with the same guards."""
from bench import scopes


def read(name, r):
    attr = scopes.window(r)
    return None if attr is None else attr.us_per_image(scopes.IM2COL)
