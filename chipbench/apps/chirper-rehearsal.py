"""``apps/chirper.py`` over its configuration's ``rehearse`` block: the
follower graph depends on the number of accounts, so the tiny CPU
rehearsal (``--rehearse-cpu``) has a graph of its own. Named by
``configs/chirper-256k.json``'s ``rehearse.app``."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_apps_chirper_base",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "chirper.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

GRAINS = _base.grains_of(_base.load_config(True))
