"""``scopes.py``: device seconds by stage of the fit program, on a hand-made
compiled HLO text and ``op_s``, and on the program's own lowering on the
CPU; host spans read from the program's span store."""
import pytest

import scopes

HLO = """HloModule jit__fit_core, is_scheduled=true, entry_computation_layout={(f32[8,64]{1,0})->f32[]}

%fused_computation.4 (param_0.13: f32[8,8]) -> f32[8,8] {
  %param_0.13 = f32[8,8]{1,0} parameter(0)
  ROOT %copy.12 = f32[8,8]{0,1} copy(%param_0.13), metadata={op_name="jit(_fit_core)/encoder/jit(eigh)/div" stack_frame_id=4}
}

%fused_computation.2 (param_0.7: f32[8,64]) -> f32[8,64] {
  %param_0.7 = f32[8,64]{1,0} parameter(0)
  %neg.0 = f32[8,64]{1,0} negate(%param_0.7), metadata={op_name="jit(_fit_core)/layer2/forward/neg"}
  ROOT %div.6 = f32[8,64]{1,0} divide(%neg.0, %param_0.7), metadata={op_name="jit(_fit_core)/vmap(layer2)/stats/div"}
}

%wrapped_broadcast_computation (param_0.2: f32[]) -> f32[5] {
  %param_0.2 = f32[] parameter(0)
  ROOT %broadcast.3 = f32[5]{0} broadcast(%param_0.2), dimensions={}
}

ENTRY %main.5 (x.1: f32[8,64]) -> f32[] {
  %x.1 = f32[8,64]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.5 = f32[8,8]{0,1} fusion(%x.1), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(_fit_core)/layer9/forward/mul"}
  %custom-call.42 = (f32[8,8]{0,1}, s32[]) custom-call(%fusion.5), custom_call_target="Cholesky", metadata={op_name="jit(_fit_core)/layer2/solve/jit(cholesky)/cholesky"}
  %fusion.8 = f32[8,64]{1,0} fusion(%x.1), kind=kOutput, calls=%fused_computation.2
  %dot.1 = f32[8,8]{1,0} dot(%fusion.8, %fusion.8), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(_fit_core)/errors/jit(sample_mse)/dot_general"}
  %copy.3 = f32[8,8]{1,0} copy(%dot.1), metadata={op_name="jit(_fit_core)/layer2/transpose"}
  %wrapped_broadcast = f32[5]{0} fusion(%constant.1), kind=kLoop, calls=%wrapped_broadcast_computation
  ROOT %reduce.1 = f32[] reduce(%copy.3), metadata={op_name="jit(_fit_core)/layer6/forward/reduce_sum"}
}

"""


def test_parse_maps_instructions_and_a_fusion_takes_its_roots_scope():
    module, names = scopes.parse(HLO)
    assert module == "jit__fit_core"
    # the fusion's own metadata says forward; its root says encoder
    assert scopes.stage(names["fusion.5"]) == "encoder"
    # no metadata of its own: the root's, through the vmap wrapper
    assert scopes.stage(names["fusion.8"]) == "stats"
    assert scopes.stage(names["custom-call.42"]) == "solve"
    assert scopes.stage(names["dot.1"]) == "errors"
    assert scopes.stage(names["copy.3"]) == "unscoped"
    assert scopes.stage(names["reduce.1"]) == "forward"
    assert "wrapped_broadcast" not in names


def test_split_sums_op_seconds_by_stage_and_leaves_other_programs_out():
    module, names = scopes.parse(HLO)
    op_s = {
        "jit__fit_core:fusion.5": 1.0,
        "jit__fit_core:custom-call.42 Cholesky": 2.0,  # the target suffix
        "jit__fit_core:fusion.8": 4.0,
        "jit__fit_core:dot.1": 8.0,
        "jit__fit_core:copy.3": 16.0,
        "jit__fit_core:reduce.1": 32.0,
        "jit__fit_core:wrapped_broadcast": 64.0,
        "jit__threefry_split:fusion.5": 128.0,  # another program, same name
        "copy-start": 256.0,  # no program
    }
    got = scopes.split(op_s, module, names)
    assert got == {"encoder": 1.0, "stats": 4.0, "solve": 2.0, "forward": 32.0,
                   "errors": 8.0, "unscoped": 16.0, "unmapped": 64.0,
                   "other": 384.0}


@pytest.mark.parametrize("tenants", [1, 3])
def test_device_split_of_the_programs_own_lowering(tenants):
    layer_sizes = [8, 3, 5, 6, 8]
    cfg = {"layer_sizes": layer_sizes, "lam_hidden": 0.5, "lam_last": 0.5,
           "act_hidden": "logsig", "act_last": "linear", "tenants": tenants}
    n = 64
    text = scopes._compiled_text({"config": cfg,
                                  "record": {"samples_per_fit": tenants * n}})
    module, names = scopes.parse(text)
    assert module == ("jit__fit_core" if tenants == 1 else "jit__fleet_fit")
    custom = [i for i in names if i.startswith(("custom-call", "eigh", "cholesky"))]
    assert custom
    op_s = {f"{module}:{instr}": 1.0 for instr in names}
    op_s["jit__other:fusion.1"] = 5.0
    run = {"config": cfg, "record": {"samples_per_fit": tenants * n, "fits": 2},
           "trace": {"op_s": op_s}}
    got = scopes.device_split(run)
    assert got["other"] == 5.0 and got["unmapped"] == 0.0
    assert min(got[s] for s in ("encoder", "stats", "solve", "forward", "errors")) > 0
    assert sum(got.values()) == pytest.approx(len(names) + 5.0)
    total = sum(scopes.device_ms(run, *s) for s in
                (["encoder"], ["stats"], ["solve"],
                 ["forward", "errors", "unscoped", "unmapped"]))
    assert total == pytest.approx(1e3 * len(names) / 2)


def test_spans_read_from_the_program_store():
    from repro import obs

    assert scopes.span_ms("t_scopes.never") is None
    for _ in range(2):
        with obs.span("t_scopes.outer"), obs.span("t_scopes.step"):
            pass
    ms = scopes.span_ms("t_scopes.step")
    assert ms is not None and ms >= 0
    assert scopes.compiled_s("t_scopes.step") == 0.0


def test_no_trace_reads_nothing():
    assert scopes.device_ms({"trace": None, "record": {"fits": 3}}, "encoder") is None
    assert scopes.device_ms({"trace": {"op_s": {}}, "record": {"fits": 3}},
                            "encoder") is None


def test_a_cached_program_without_scopes_is_compiled_again(tmp_path, monkeypatch):
    """The persistent compile cache keys a program without its metadata, so
    it can hand back an executable compiled without the scopes; the split
    then compiles the program again with the cache off."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cfg = {"layer_sizes": [8, 3, 6, 8], "lam_hidden": 0.5, "lam_last": 0.5,
           "act_hidden": "logsig", "act_last": "linear", "tenants": 1}
    run = {"config": cfg, "record": {"samples_per_fit": 48}}
    saved = {k: getattr(jax.config, k) for k in
             ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
              "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", lambda _name: contextlib.nullcontext())
            jax.clear_caches()
            plain = scopes._compiled_text(run)
        assert not scopes._scoped(plain)
        assert list(tmp_path.iterdir())  # the cache holds the plain program
        jax.clear_caches()
        again = scopes._compiled_text(run)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        jax.clear_caches()
    assert scopes._scoped(again)
    assert scopes.parse(again)[0] == scopes.parse(plain)[0]
