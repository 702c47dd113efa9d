"""The program's own trace marks: host spans around the scheduler's step and
each stage of `BatchedEngine.step` (jax.profiler.TraceAnnotation), and
named scopes on the pass's layers (jax.named_scope), which reach the
compiled program's operation metadata."""

import glob
import re

import jax
import jax.numpy as jnp

ENGINE_STAGES = ["engine.plan", "engine.draft", "engine.pack",
                 "engine.prefetch", "engine.dispatch", "engine.fetch_logits",
                 "engine.verify", "engine.rollback", "engine.cost",
                 "engine.feedback"]


def _spans(trace_dir):
    """[(name, start, end)] of the program's spans in the profile written
    under `trace_dir`, in start order (outer before inner)."""
    from jax.profiler import ProfileData
    pb = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(pb[-1])
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in prof.planes for line in plane.lines
           for e in line.events
           if e.name.startswith(("engine.", "sched."))]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    """The spans directly inside `parent`, in start order."""
    inside = [s for s in spans if s is not parent
              and parent[1] <= s[1] and s[2] <= parent[2]]
    return [s for s in inside
            if not any(o is not s and o[1] <= s[1] and s[2] <= o[2]
                       for o in inside)]


def test_two_scheduler_steps_trace_every_stage(tiny_moe, tmp_path):
    """Two steps of the scheduler, with host-tier experts so that the
    prefetch stage runs: each `sched.step` holds admit, the engine's step
    and retire; each `engine.step` holds every stage, in stage order."""
    from repro.core import (CascadeController, ExpertPlacement,
                            ResidencyState)
    from repro.serving import (BatchedEngine, ContinuousBatchingScheduler,
                               NGramDrafter, Request)
    cfg, params = tiny_moe
    off = ExpertPlacement.contiguous(cfg.num_experts, 1).offload(
        [cfg.num_experts - 1])
    eng = BatchedEngine(cfg, params, NGramDrafter, max_batch=2, max_len=128,
                        temperature=0.0, clock="model", seed=0, chunk=8,
                        residency=ResidencyState(off, cfg), prefetch=True)
    sched = ContinuousBatchingScheduler(
        eng, controller_factory=CascadeController)
    for i in range(2):
        sched.submit(Request(request_id=f"r{i}", prompt=[3 + i, 4, 5] * 4,
                             max_new=4))
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        sched.step()
    jax.profiler.stop_trace()

    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "sched.step"]
    assert len(steps) == 2
    first_step = eng._step_idx - 2
    for n, step in enumerate(steps):
        kids = _children(spans, step)
        assert [k[0] for k in kids] == ["sched.admit", "engine.step",
                                        "sched.retire"]
        joins = [k[0] for k in _children(spans, kids[0])]
        assert joins == (["engine.join"] * 2 if n == 0 else [])
        stages = _children(spans, kids[1])
        assert [k[0] for k in stages] == ENGINE_STAGES
        # stages follow one another
        assert all(a[2] <= b[1] for a, b in zip(stages, stages[1:]))
    # the engine's step span carries the step index StepTelemetry records
    from jax.profiler import ProfileData
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    args = [dict(e.stats)["step"] for plane in
            ProfileData.from_file(pb[-1]).planes for line in plane.lines
            for e in line.events if e.name == "engine.step"]
    assert sorted(args) == [first_step, first_step + 1]
    assert [s.step for s in eng.telemetry.steps[-2:]] == sorted(args)


def test_decode_step_names_its_layers(tiny_moe):
    """The compiled verification pass names attention, the MoE FFN and the
    LM head in its operations' metadata."""
    cfg, params = tiny_moe
    hlo = _compiled_pass(cfg, params)
    stacks = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("attention", "moe_ffn", "lm_head"):
        assert any(f"/{scope}/" in s for s in stacks), scope
    # attention and the MoE FFN sit inside the layer loop; the head after
    assert any(re.search(r"/while/body/.*/moe_ffn/", s) for s in stacks)
    assert not any(re.search(r"/while/.*/lm_head/", s) for s in stacks)


def _compiled_pass(cfg, params):
    from repro.serving import BatchedEngine, NGramDrafter
    eng = BatchedEngine(cfg, params, NGramDrafter, max_batch=2, max_len=64,
                        chunk=8, packed=True)
    toks = jnp.zeros((2, 2), jnp.int32)
    mask = jnp.ones((2, 2), bool)
    return eng._decode.lower(eng.params, eng.cache, toks, mask).compile() \
        .as_text()


def _instructions(hlo):
    """The compiled program's computations, without the metadata and the
    source tables the module text begins with."""
    lines = hlo.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    return [re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in lines[first:]]


def test_scopes_leave_the_pass_unchanged(tiny_moe, monkeypatch):
    """The scopes are metadata only: the pass compiled without them is the
    same program, operation for operation."""
    import contextlib
    from repro.models import transformer as T
    cfg, params = tiny_moe
    scoped = _compiled_pass(cfg, params)
    monkeypatch.setattr(T.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_pass(cfg, params)
    assert "/moe_ffn/" in scoped and "/moe_ffn/" not in plain
    assert _instructions(scoped) == _instructions(plain)
