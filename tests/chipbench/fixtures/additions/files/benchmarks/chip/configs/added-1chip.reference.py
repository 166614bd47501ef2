"""Plain reference of the tests' own second configuration: stateless turns,
each under stock python in a directory of its own. The same semantics as
`toolcalls-1chip`'s, whose `run(chain, scratch)` it takes from the file beside it."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "chipbench_stateless_reference", Path(__file__).with_name("toolcalls-1chip.reference.py"))
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
run = _module.run
