"""A model family for tests: the dense family's maths under another name,
kept outside `families/` so that a test has to add this directory to the
package's search path (`families.__path__`) to find it."""

from families import dense as _dense

KEYS, PUBLISHED, CONVENTIONS = _dense.KEYS, _dense.PUBLISHED, _dense.CONVENTIONS
check = _dense.check
weights, logits = _dense.weights, _dense.logits
request_flops, stage_cost = _dense.request_flops, _dense.stage_cost
