"""A configuration's own copy of the plain reference, as a later
configuration would bring one: here ``bench/reference.py`` re-exported
under a mark, for the test that a configuration's ``reference`` key is
honoured."""
from bench.reference import *  # noqa: F401,F403

MARK = "bench/tests/reference_copy.py"
