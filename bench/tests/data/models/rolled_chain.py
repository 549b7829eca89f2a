"""A model for the tests of the model seam: the GEMM chain whose job
``k`` reads the seed's input rolled by ``k`` rows, so that each job's
answer depends on its ordinal (the chain maps rows to rows, so the
answer of job ``k`` is the chain's answer rolled by ``k`` rows).

`SHIFT` serves job ``k`` the input of ordinal ``k + SHIFT``;
`CONTROL_AS_REFERENCE` puts the control in the reference's place. A
test sets either to see the check fail.
"""
import jax.numpy as jnp

import harness

gemm = harness.load_model("gemm_chain")

#: job ``k`` is served the input of ordinal ``k + SHIFT``
SHIFT = 0
#: the check compares with ``control(k)`` in place of ``reference(k)``
CONTROL_AS_REFERENCE = False


def build(specs, **kw):
    return [
        Tenant(t.shapes, t.block, t.weights, t.x, t.stage_of_layer)
        for t in gemm.build(specs, **kw)
    ]


class Tenant(gemm.Tenant):
    def _input(self, k):
        # a shift on the device: one program for every ordinal
        return jnp.roll(self.x, jnp.asarray(k, jnp.int32), axis=0)

    def job_input(self, k):
        return self._input(k + SHIFT)

    def reference(self, k):
        if CONTROL_AS_REFERENCE:
            return self.control(k)
        return gemm.chain(self._input(k), self.weights)

    def control(self, k):
        return gemm.chain_bf16x3(self._input(k), self.weights)
