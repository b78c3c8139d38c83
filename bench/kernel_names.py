"""Patterns that find the simulator's kernels among the trace's device ops."""

# The megakernel (kernels/fused_tick.py) is a pallas_call with no name= of
# its own. Alone, its device op is a ``custom-call`` to Mosaic's target,
# named only ``%closed_call.N``; under ``vmap`` (the serving lanes) XLA wraps
# it in a fusion of kind ``kCustom``, whose text does not name the target.
# In the fused tick it is the only Mosaic kernel and the only such fusion.
FUSED_TICK = r'custom_call_target="tpu_custom_call"|kind=kCustom'
