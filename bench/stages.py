"""The train step's stage names and ``stage_of``, kept where the program's
own stage test imports them; the reduction by scope is ``bench/trace.py``'s
(``hlo_scopes``, ``Reduction.scope_s``)."""
from bench.trace import STAGES, stage_of

__all__ = ["STAGES", "stage_of"]
