"""The masked-Adam kernel's share of its roofline, in percent: the bytes
the traced rounds' Adam steps require (trained parameters only,
``bench.counts.adam_bytes``) over the kernel's device seconds times the
chip's HBM bandwidth.  Adam is bound by memory, so bytes set the roofline.
The kernel is the Pallas call of ``repro.kernels.masked_adam``.  Its op
carries no name of its own: it is the ``tpu_custom_call`` whose three
outputs alias operands 2, 4 and 5 (p, m and v; the block mask is operand 0).
It is the only Pallas call in the cells' programs."""

from bench import counts

KERNEL = ('custom_call_target="tpu_custom_call"',
          "output_to_operand_aliasing={{0}: (2, {}), {1}: (4, {}), {2}: (5, {})}")


def read(ctx):
    s = ctx["trace"].op_s(KERNEL)
    if s <= 0:
        return None
    trained = ctx["group_trained_params"]
    per_step = [sum(trained) if g < 0 else trained[g] for g in ctx["traced_groups"]]
    steps = ctx["cell"].traffic["cohort"] * ctx["client_steps"]
    need = sum(counts.adam_bytes(n) * steps for n in per_step)
    return 100.0 * need / (s * ctx["peaks"]["hbm_bytes_per_s"])
