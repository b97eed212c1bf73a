"""Importing this package registers every built-in pass.

To add a pass: create a module here with a ``@register``-decorated
``GlintPass`` subclass and import it below.  Give the rule a
kebab-case name — it becomes the suppression key
(``# glint: disable=<name>``), the ``--rules`` selector, and the
baseline fingerprint prefix.  Add a positive + negative fixture to
``tests/test_glint.py``.
"""
from . import (env_knobs, event_schema, guarded_by,  # noqa: F401
               host_sync, metric_label, metric_name, monotonic, rng)
