"""One module per way of laying out a query's cells, found by the ``cells``
name of a traffic class.

``bench/layouts/<name>.py`` defines ``draw(rng, shape, k, cls) ->`` sorted
flat cell ids of an array of ``shape``: ``k`` cells, drawn with ``rng`` (a
:class:`numpy.random.Generator` from the run's seed); ``cls`` is the traffic
class, for parameters of the layout's own.
"""

import importlib


def get(name: str):
    return importlib.import_module(f"{__name__}.{name}")
