"""The benchmark's own code: everything here is the yardstick, none of it
names a cell, a configuration or a model.  Those live in data files beside
this package (``configs/``, ``traffic/``, ``cells/``, ``layer_metrics/``)
and in the small modules they name (``readers/``, ``reference/``)."""
