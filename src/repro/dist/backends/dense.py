"""'dense' execution backend: Algorithm 1/2 against P as given.

P may be a dense matrix or a matvec closure (applying P along the *last*
axis, broadcasting over leading batch dims); this is the single-device
reference path (what `UnionMultiplier.apply` always did) wrapped in the
uniform ExecutionPlan signature, including the batched (..., N) contract.
The logical N is the execution domain: no padding, no cropping.
"""
from __future__ import annotations

from ...core import chebyshev as cheb
from . import register_backend


@register_backend("dense")
def build(op, *, mesh=None, partition=None, **options):
    from ..operator import LocalForm, plan_from_form

    del mesh, partition  # single-device backend
    mv = cheb.LocalMatvec(op.matvec)
    form = LocalForm(local=lambda mine: mv, recurrence=cheb.cheb_apply,
                     lasso=False)
    return plan_from_form(op, "dense", form,
                          info={"matvecs_per_apply": op.K})
