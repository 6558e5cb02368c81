"""Device time per tick stage (``perfbench/stage_trace.py``) on synthetic
traces: self time of nested ops, the innermost scope, both sources of an
op's scope, loop and unscoped time, and the sum that equals busy time."""

import pytest

import stage_trace as st  # noqa: E402  (perfbench is on the path via conftest)

WIN = (0.0, 20.0)

#: (HLO text, start, end, op_name) of one scan: a ``while`` spanning the
#: loop, the body's stage ops inside it, a sampler loop inside the
#: arrival stage with its own body op, and a copy no scope covers
EVENTS = [
    ("%while.280 = (s32[]) while(%t), body=%b", 1.0, 15.0,
     "jit(run)/vmap()/while"),
    ("%fusion.1 = s32[4] fusion(%a), kind=kLoop", 1.5, 3.0,
     "jit(run)/vmap()/while/body/closed_call/tick.arrival/add"),
    ("%while.9 = (s32[]) while(%u), body=%c", 3.0, 5.0,
     "jit(run)/vmap()/while/body/tick.arrival/jit(_uniform)/while"),
    ("%fusion.2 = u32[4] fusion(%k), kind=kLoop", 3.5, 4.0,
     "jit(run)/vmap()/while/body/tick.arrival/jit(_uniform)/while/body/add"),
    ("%reshape.7 = s32[40,4,131072] reshape(%f)", 5.0, 9.0,
     "jit(run)/vmap()/while/body/closed_call/tick.route/jit(pick)/"
     "tick.filter/reshape"),
    ("%fusion.3 = f32[8] fusion(%q), kind=kLoop", 9.0, 10.5,
     "jit(run)/vmap()/while/body/tick.server/mul"),
    ("%copy.4 = s32[80,1024] copy(%p)", 11.0, 12.0, ""),
    ("%fusion.5 = s32[40] fusion(%n), kind=kLoop", 16.0, 17.0,
     "jit(run)/vmap(fleetsim.draw)/jit(_poisson)/add"),
]
SPANS = [("bench.window", 0.0, 20.0), ("fleetsim.lower", 0.0, 0.5),
         ("fleetsim.device", 0.5, 17.5), ("fleetsim.fetch", 17.5, 19.0),
         ("bench.device", 0.5, 17.5)]


def _ops(source):
    """The events as scoped ops, the scope read from the ``tf_op`` stat or
    from the program's text by HLO name."""
    hlo = "\n".join(f'  {text.split(" = ")[0]} = s32[] add(), '
                    f'metadata={{op_name="{name}" source_file="x.py"}}'
                    for text, _, _, name in EVENTS if name)
    names = st.op_names(hlo)
    out = []
    for text, s, e, name in EVENTS:
        stats = {"tf_op": name} if source == "tf_op" and name else {}
        out.append((text, s, e, st.resolve_scope(
            text, stats, names if source == "hlo" else {})))
    return {"/device:TPU:0": out}


@pytest.fixture(params=["tf_op", "hlo"])
def stages(request):
    return st.reduce_stages(_ops(request.param), SPANS, WIN)


def test_innermost_scope_component_wins():
    assert st.scope_of("jit(run)/tick.route/jit(f)/tick.filter/add") == \
        "tick.filter"
    assert st.scope_of("jit(run)/vmap(fleetsim.init)/vmap(jit(_t))/x") == \
        "fleetsim.init"
    assert st.scope_of("jit(run)/vmap()/while") == ""
    assert st.scope_of("") == ""


def test_self_time_excludes_nested_ops():
    own = {op[0].split(" = ")[0]: t
           for op, t in st.self_times(_ops("tf_op")["/device:TPU:0"], WIN)}
    # the loop's 14 s less its body ops' 1.5 + 2 + 4 + 1.5 + 1
    assert own["%while.280"] == pytest.approx(4.0)
    assert own["%while.9"] == pytest.approx(1.5)     # 2 s less 0.5
    assert own["%fusion.2"] == pytest.approx(0.5)


def test_stage_self_times(stages):
    assert stages.stage_s == pytest.approx({
        "tick.arrival": 1.5 + 1.5 + 0.5, "tick.filter": 4.0,
        "tick.server": 1.5, "fleetsim.draw": 1.0})
    assert stages.loop_self_s == pytest.approx(4.0)
    assert stages.unscoped_s == pytest.approx(1.0)
    assert stages.top_ops["unscoped"][0][0] == "%copy.4 s32[80,1024]"


def test_both_scope_sources_agree():
    a = st.reduce_stages(_ops("tf_op"), SPANS, WIN)
    b = st.reduce_stages(_ops("hlo"), SPANS, WIN)
    assert a == b


def test_scopes_loop_and_unscoped_add_up_to_busy_time(stages):
    # busy: [1, 15) and [16, 17)
    assert stages.busy_s == pytest.approx(15.0)
    assert stages.total_s == pytest.approx(stages.busy_s)


def test_window_clips_self_time():
    red = st.reduce_stages(_ops("tf_op"), SPANS, (0.0, 8.0))
    assert red.busy_s == pytest.approx(7.0)
    assert red.total_s == pytest.approx(7.0)
    assert red.stage_s["tick.filter"] == pytest.approx(3.0)
    assert "fleetsim.draw" not in red.stage_s


def test_idle_time_by_program_phase(stages):
    assert stages.idle_by_phase == pytest.approx(
        {"lower": 0.5, "device": 2.0, "fetch": 1.5})


def test_nothing_to_read_gives_none():
    assert st.reduce_stages({"/device:TPU:0": []}, SPANS, WIN) is None


def test_metadata_of_compiled_text():
    hlo = ('ENTRY %main {\n'
           '  %p = s32[4] parameter(0)\n'
           '  ROOT %fusion.3 = s32[4] fusion(%p), kind=kLoop, '
           'calls=%fc, metadata={op_name="jit(run)/tick.client/add"}\n}')
    assert st.op_names(hlo) == {"fusion.3": "jit(run)/tick.client/add"}
    assert st.is_loop("%while.1 = (s32[]) while(%t), body=%b")
    assert not st.is_loop("%fusion.1 = s32[4] fusion(%a)")


def test_profile_runs_a_tiny_cell(tiny):
    """The profiled call end to end at the test size: the compiled
    program's metadata names the stage scopes; the CPU trace holds no
    chip plane."""
    import jax

    enabled = jax.config.jax_enable_compilation_cache
    try:
        stages, n_ticks, n_scoped, window_s = st.profile(
            "testbed.switch5", 5)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    assert n_ticks == tiny("testbed.switch5").n_ticks
    assert n_scoped > 0 and window_s > 0
    assert stages is None
