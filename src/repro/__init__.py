"""repro — Generalized AsyncSGD stochastic-networks framework.

Subpackages are imported lazily; see README.md for the map.
"""

import os

__version__ = "1.0.0"

#: Root of the checkout this package runs from (``<root>/src/repro``): the
#: one fixed home of run-time files (compile cache, local datasets).
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
