"""perf_probe analysis units: the BN-epilogue classifier must answer by
dataflow, not substring presence (settle whether BN scale/
shift rides the conv epilogue in the committed HLO)."""
import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, os.path.join(ROOT, "tools"))


def test_bn_fusion_analysis_dataflow():
    from perf_probe import bn_fusion_analysis

    synthetic = """HloModule m

%fused_computation.1 (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  %convolution.1 = f32[4]{0} convolution(%p0, %p1), window={}
  %mul.1 = f32[4]{0} multiply(%convolution.1, %p1)
  ROOT %add.1 = f32[4]{0} add(%mul.1, %p0)
}

%fused_computation.2 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %scaled = f32[4]{0} multiply(%p0, %p0)
  ROOT %convolution.2 = f32[4]{0} convolution(%scaled, %p0), window={}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %convolution.3 = f32[4]{0} convolution(%a, %a), window={}
  %s = f32[4]{0} add(%convolution.3, %a)
  ROOT %f = f32[4]{0} fusion(%s), kind=kLoop, calls=%fused_computation.1
}
"""
    r = bn_fusion_analysis(synthetic)
    # conv.1: result consumed by multiply in its fusion -> epilogue-fused.
    # conv.2: multiply feeds the conv INPUT; result untouched -> plain.
    # conv.3: lives in ENTRY -> bare, even with an entry-level add consumer
    # (entry instructions are separate kernels).
    assert r == {"convs_total": 3,
                 "convs_fused_with_elementwise_epilogue": 1,
                 "convs_fused_plain": 1,
                 "convs_bare_in_entry": 1}, r

    # modern compiled.as_text() dumps omit the % name sigil entirely —
    # classification must be identical on the sigil-less form
    r2 = bn_fusion_analysis(synthetic.replace("%", ""))
    assert r2 == r, r2
