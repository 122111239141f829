"""Profiling utilities (reference: nsight runtime-env plugin +
_private/profiling.py; TPU analogue = jax.profiler)."""
import glob
import os

import pytest


class TestProfiling:
    def test_trace_writes_artifacts(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from ray_tpu.util import profiling
        with profiling.capture(str(tmp_path / "tb")) as cap:
            x = jnp.ones((128, 128))
            jax.block_until_ready(x @ x)
        assert not hasattr(profiling, "trace")      # capture's older name
        files = glob.glob(os.path.join(cap.logdir, "**", "*"),
                          recursive=True)
        assert cap.xplane in files
        assert any("trace" in f or f.endswith(".pb") or ".xplane." in f
                   for f in files), files

    def test_annotate(self):
        import jax.numpy as jnp

        from ray_tpu.util import profiling
        with profiling.annotate("section"):
            jnp.ones(4).sum()

    def test_the_scan_kernels_have_scopes_and_are_counted(self):
        """The selective scan's two kernels and the two element-wise
        stages of a Mamba-2 layer are in the table of device scopes, and
        the static counter counts the kernels under them."""
        from ray_tpu.util import profiling
        assert {"ssm_scan_fwd", "ssm_scan_bwd", "ssm_conv",
                "ssm_gate_norm"} <= set(profiling.DEVICE_SCOPES)
        call = ('custom-call(%a), custom_call_target="tpu_custom_call"')
        text = "\n".join(
            [f"  %ssm_scan_fwd.{i} = bf16[8,128]{{1,0}} {call}"
             for i in range(9)]
            + [f"  %transpose_jvp_ssm_scan_bwd_.{i} = bf16[8,128]{{1,0}} "
               f"{call}" for i in range(9)])
        assert profiling.kernel_calls(text) == {"ssm_scan_fwd": 9,
                                                "ssm_scan_bwd": 9}
