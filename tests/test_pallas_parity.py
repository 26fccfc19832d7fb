"""The Pallas kernels' backend-aware interpret default: native lowering on
TPU/GPU, interpret mode elsewhere, and no wrapper that asks for the
interpreter on its own."""
import jax

from repro.kernels.runtime import default_interpret, resolve_interpret


def test_default_interpret_is_backend_aware():
    assert default_interpret("tpu") is False
    assert default_interpret("gpu") is False
    assert default_interpret("cpu") is True
    assert resolve_interpret(True) is True and resolve_interpret(False) is False
    # None resolves from the live backend; on the CPU test host that is
    # interpret mode, and PallasSubstrate bakes the resolved value in
    assert resolve_interpret(None) == default_interpret(jax.default_backend())
    from repro.engine import PallasSubstrate

    assert PallasSubstrate().interpret == default_interpret(jax.default_backend())
    assert PallasSubstrate(interpret=False).interpret is False


def test_no_pallas_wrapper_defaults_to_interpret_mode():
    """Every Pallas entry point defaults to ``interpret=None`` (resolved from
    the backend), so a TPU never runs the interpreter unasked — the LM
    layer's flash call included, which passes no ``interpret`` at all."""
    import inspect

    from repro.kernels.flash_attention.kernel import flash_attention_folded
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.topk_sim.kernel import topk_sim_pallas
    from repro.kernels.topk_sim.ops import topk_sim_pairs

    for fn in (flash_attention_folded, flash_attention, topk_sim_pallas, topk_sim_pairs):
        assert inspect.signature(fn).parameters["interpret"].default is None, fn
