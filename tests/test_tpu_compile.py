"""The main path's large jitted programs compile for the TPU v5e at the
real widths, without a chip: the installed TPU compiler compiles for a
described `v5e:2x2` topology and raises what the chip's compiler would
raise (a program that does not fit the 16 GB of HBM, an op it cannot
lower). Nothing runs, so this says nothing about results or times —
`chip_smoke.py` is the run.

The programs are the ones `chip_smoke.py` drives, built from its own
configuration: the flagship cluster and model (50 executors, job cap
200, Decima 16 / [32,16] / [64,64], job_bucket 32, 16 lanes, health
on, rbg keys), the serve phase's store shape, and the headline bench's
flat micro-step chunk at 1024 lanes unsplit and in sub-batches of 512.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports every
test file. Keep these tests in this one file for the same reason.
"""

from __future__ import annotations

import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def flagship(tmp_path_factory):
    """The smoke run's trainer (its config, uncut), with the
    process-wide settings it and these compiles touch put back
    afterwards: the flagship config switches jax to rbg keys, and
    a compile for a described chip must not go through the persistent
    cache (it could be written but never read back)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import chip_smoke
    from sparksched_tpu.trainers import make_trainer

    prng = jax.config.jax_default_prng_impl
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield make_trainer(chip_smoke.load_cfg(
            chip_smoke.TRAIN_CONFIG,
            str(tmp_path_factory.mktemp("tpu_compile")),
        ))
    finally:
        jax.config.update("jax_default_prng_impl", prng)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) on the chip."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            jnp.shape(a), jnp.result_type(a), sharding=sharding
        ),
        tree,
    )


def _fits(compiled, temp_gib: float = 16.0) -> None:
    ma = compiled.memory_analysis()
    need = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert 0 < need < 16 * 2**30, ma
    assert ma.temp_size_in_bytes < temp_gib * 2**30, ma


def _train_state(trainer, one_chip):
    import jax

    return _on(one_chip, jax.eval_shape(trainer.init_state))


def test_flagship_widths(flagship):
    """What is compiled below is the flagship, not a cut of it."""
    import jax

    p = flagship.params_env
    assert (p.num_executors, p.max_jobs, flagship.num_envs) == (50, 200, 16)
    assert flagship.scheduler.job_bucket == 32
    assert flagship.health_enabled
    assert jax.config.jax_default_prng_impl == "rbg"


def test_batch_policy_compiles(flagship, one_chip):
    """`DecimaScheduler.batch_policy` at K=32 over 16 lanes."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe

    p, bank = flagship.params_env, flagship.bank
    state = _train_state(flagship, one_chip)
    obs = jax.eval_shape(
        lambda k: jax.vmap(
            lambda kk: observe(p, core.reset(p, bank, kk))
        )(jax.random.split(k, flagship.num_envs)),
        jax.random.PRNGKey(0),
    )
    _fits(jax.jit(flagship.scheduler.batch_policy).lower(
        state.rng, _on(one_chip, obs), state.params
    ).compile())


@pytest.fixture(scope="module")
def collector(flagship, one_chip):
    """The trainer's collector compiled for the chip, once for the
    tests that read it."""
    state = _train_state(flagship, one_chip)
    return flagship._collect_jit.lower(
        state.params, state.iteration, state.rng, None
    ).compile()


def test_collect_and_ppo_update_compile(flagship, one_chip, collector):
    """The trainer's own two programs: the single-eval flat collector
    (`collect_flat_sync_batch`, 16 lanes, fused bulk pass, health on)
    and `ppo_update` with the health gate, on the collector's rollout,
    at the committed 16 lanes x 9600 steps (a 6.1 GiB rollout). The
    bounds on temporaries hold the two repairs that made that fit:
    the stored node grids as flat rows of whole 128-lane tiles (with
    [J,S] grids the compiler puts the time axis minor-most and both
    programs copy the whole rollout: 9.2 GiB of temporaries each), and
    the update's gradient in chunks of `ppo.CHUNK_SAMPLES` samples
    (a 15,360-sample minibatch whole needs some 400 GB)."""
    import jax

    assert flagship.rollout_steps == 9600
    state = _train_state(flagship, one_chip)
    args = (state.params, state.iteration, state.rng, None)
    _fits(collector, temp_gib=1.0)
    ro = jax.eval_shape(flagship._collect, *args)[0]
    _fits(flagship._update_jit.lower(state, _on(one_chip, ro)).compile(),
          temp_gib=5.0)


def test_level_scan_body_has_one_layout(flagship, one_chip):
    """What takes a counter's place for the level scan (PR 33): in the
    net compiled for the v5e at the benchmark's 128 lanes x 200 jobs,
    every node-sized array of the scan's body lies with the 128-lane
    batch axis minor-most (whole tiles, nothing padded), and the body
    copies none of them into another layout. The per-job
    `[S,S] @ [S,D]` product the children's sum used to be wanted the
    stage axis there: a copy in and a copy out, every step."""
    import re

    import jax
    import jax.numpy as jnp

    from sparksched_tpu.schedulers.decima import DecimaFeatures

    lanes, (j, s, n) = 128, (200, 20, 50)
    net = flagship.scheduler.net
    assert (net.num_levels, flagship.params_env.max_jobs) == (5, j)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct((lanes, *shape), dtype, sharding=one_chip)

    feats = DecimaFeatures(
        x=on_chip((j, s, 5), jnp.float32),
        node_mask=on_chip((j, s), jnp.bool_),
        job_mask=on_chip((j,), jnp.bool_),
        stage_mask=on_chip((j, s), jnp.bool_),
        exec_mask=on_chip((j, n), jnp.bool_),
        adj=on_chip((j, s, s), jnp.bool_),
        node_level=on_chip((j, s), jnp.int32),
    )
    params = _on(one_chip, jax.eval_shape(flagship.init_state).params)
    compiled = jax.jit(net.apply).lower(params, feats).compile()
    # the select feeding the sum is fused into the reduce: alone its
    # [128,200,20,20,16] operand would be 0.6 GiB (the net's whole
    # temporaries: 0.16 GiB, where the per-job product's were 0.31)
    _fits(compiled, temp_gib=0.25)
    text = compiled.as_text()
    (body_name,) = set(re.findall(r"body=%([\w.\-]+)", text))
    body = text[text.index(f"\n%{body_name} ("):]
    body = body[:body.index("\n}\n")]
    node_sized = re.findall(
        rf"= \w+\[{lanes},{j},{s},\d+\](\{{[\d,]*)[^ ]* ([\w\-]+)\(", body
    )
    assert len(node_sized) >= 8, body  # six Dense layers, the sum, the select
    assert {layout for layout, _ in node_sized} == {"{0,3,2,1"}, node_sized
    assert not {op for _, op in node_sized} & {"copy", "transpose"}, node_sized


def _computations(text: str) -> dict[str, str]:
    """The computations of an HLO module, by name: their bodies."""
    import re

    return {
        m.group(1): m.group(2)
        for m in re.finditer(
            r"\n(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)\n\}\n", text, re.S
        )
    }


def _called(text: str, root: str) -> dict[str, str]:
    """The computations of an HLO module reachable from `root`, by
    name: their bodies."""
    import re

    comps = _computations(text)
    calls = re.compile(
        r"(?:calls|body|condition|to_apply|true_computation"
        r"|false_computation)=%([\w.\-]+)|branch_computations=\{([^}]*)\}"
    )
    seen, todo = {}, [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen[name] = comps[name]
        for m in calls.finditer(comps[name]):
            todo += [m.group(1)] if m.group(1) else [
                x.strip().lstrip("%") for x in m.group(2).split(",")
            ]
    return seen


def test_drain_loop_writes_nothing_of_the_adjacency_s_size(
    flagship, collector
):
    """What takes a counter's place for the fused pass's refresh of
    `unsat_parent_count` (PR 39): in the collector compiled for the
    v5e, no instruction of the drain `while` (its body and predicate,
    and every fusion, conditional and inner loop they call) yields an
    array with the adjacency's dimensions, lanes x 200 x 20 x 20 in
    any order: no copy of it into another layout, no select or
    product over it. Until PR 51 the loop handed the adjacency on
    (parameters and tuples) and read rows of it (gathers, whose
    results are rows); since then the drain reads a stage's children
    and a job's adjacency off the packed parent sets, and the loop
    does not hold the adjacency at all.
    Until PR 39 the refresh selected and reduced the whole of it, as
    stored (a job's 20 x 20 block padded to a tile: 105 MB at 128
    lanes), in every body."""
    p = flagship.params_env
    _holds_no_adjacency_sized_result(
        collector.as_text(),
        [flagship.num_envs, p.max_jobs, p.max_stages, p.max_stages],
    )


def _loops(text: str, op_name_end: str) -> list[str]:
    """The `while` instructions whose `op_name` ends so."""
    return [line for line in text.split("\n")
            if " while(" in line and op_name_end + '"' in line]


def _inside(text: str, loop: str) -> dict[str, str]:
    """A `while` instruction's body and predicate and what they call."""
    import re

    inside = {}
    for key in ("body", "condition"):
        root = re.search(rf"{key}=%([\w.\-]+)", loop).group(1)
        inside |= _called(text, root)
    return inside


def _holds_no_adjacency_sized_result(
    text: str, dims: list[int], batch: int | None = None
) -> None:
    """PR 39's rule on the one drain `while` of a compiled collector,
    whose lanes hold adjacencies of `dims` (in any order; `batch`: the
    lanes of the whole batch where the drain runs a block of them)."""
    import re

    whole = sorted([batch or dims[0]] + dims[1:])  # dims[0]: the lanes
    dims = sorted(dims)
    loops = _loops(text, "env/micro_step/drain)/while")
    assert len(loops) == 1, len(loops)
    inside = _inside(text, loops[0])
    assert len(inside) > 100, len(inside)  # the loop, not a stub of it

    hands_on = {"parameter", "get-tuple-element", "tuple", "bitcast"}

    def adjacency_sized(comps, dims=dims):
        """(handed on, yielded) among the instructions of `comps`."""
        found, carried = [], 0
        for name, body in comps.items():
            for line in body.split("\n"):
                m = re.match(
                    r"\s*(?:ROOT )?%[\w.\-]+ = (\(?)(\w+)\[([\d,]*)\]"
                    r"[^ ]* ([\w\-]+)\(", line
                )
                if not m or m.group(1):
                    continue  # a tuple is handed on, whatever it holds
                shape = sorted(int(d) for d in m.group(3).split(",") if d)
                if shape == dims:
                    if m.group(4) in hands_on:
                        carried += 1
                    else:
                        found.append((name, line.strip()[:160]))
        return carried, found

    # the adjacency IS in the program and the pattern finds it. Until
    # PR 51 that was shown on the loop itself, which handed it on and
    # gathered rows of it; since then nothing in the drain reads the
    # adjacency (its rows come off `parent_sets`) and the compiler
    # keeps it out of the loop's carry altogether
    carried, _ = adjacency_sized(_computations(text), whole)
    assert carried >= 2, carried
    _, found = adjacency_sized(inside)
    assert not found, found


def _gathers(text: str, scope: str) -> list[tuple[list[int], list[int]]]:
    """(result dims, operand dims) of every `gather` instruction whose
    `op_name` holds `scope`, the operand looked up by name in the
    instruction's own computation."""
    import re

    def dims(shape: str) -> list[int]:
        return [int(d) for d in shape.split(",") if d]

    found = []
    for body in _computations(text).values():
        shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", body))
        for m in re.finditer(
            r"= \w+\[([\d,]*)\][^ ]* gather\(%([\w.\-]+), [^\n]*", body
        ):
            if scope in m.group(0):
                found.append((dims(m.group(1)), dims(shapes[m.group(2)])))
    return found


def test_drain_reads_no_grid_once_an_executor(flagship, collector):
    """What takes a counter's place for the fused pass's lookup of the
    arrivals' frontier bits (PR 45): in the collector compiled for the
    v5e, no gather under the drain's scope yields `[lanes, N]` from a
    `[lanes, J, S]` operand, a grid of the state read once for every
    executor. On this chip such a gather is serialised, 12 ns an
    element: 77 us of a drain body of 128 lanes at the flagship
    cluster, in every body, until the bits came from the packed
    frontier (`core._frontier_at`). The decide step, once a row, still
    reads other grids that way (`_bulk_fulfill`'s remaining-task
    counts), which shows that the pattern is found where it is: since
    PR 51 the drain gathers from the bank alone, nine times, and the
    decide step's `_bulk_fulfill` is where the lanes' own arrays are
    still gathered from."""
    p = flagship.params_env
    lanes, n = flagship.num_envs, p.num_executors
    grid = sorted([lanes, p.max_jobs, p.max_stages])

    text = collector.as_text()

    def per_executor(gathers):
        return [(res, op) for res, op in gathers
                if res == [lanes, n] and sorted(op) == grid]

    drain = _gathers(text, "env/micro_step/drain")
    assert drain and not per_executor(drain)
    decide = _gathers(text, "env/micro_step/decide")
    assert len(decide) > 10 and per_executor(decide)


@pytest.fixture(scope="module")
def batched(flagship, one_chip, tmp_path_factory):
    """The batched-arrivals cell's collector (its cluster, job axis,
    1024 lanes and threefry keys; a short scan) compiled for the chip,
    once for the tests that read it: `(trainer, HLO text)`."""
    import jax

    import chip_smoke
    from sparksched_tpu.trainers import make_trainer

    cfg = chip_smoke.load_cfg(
        "config/decima_tpch_batched.yaml",
        str(tmp_path_factory.mktemp("tpu_compile_batched")))
    cfg["trainer"] |= {
        "num_sequences": 128, "num_rollouts": 8, "rollout_steps": 16}
    jax.config.update("jax_default_prng_impl", "threefry2x32")
    try:  # the flagship fixture's rbg keys, and back (it restores)
        trainer = make_trainer(cfg)
        state = _train_state(trainer, one_chip)
        compiled = trainer._collect_jit.lower(
            state.params, state.iteration, state.rng, None).compile()
    finally:
        jax.config.update("jax_default_prng_impl", "rbg")
    _fits(compiled, temp_gib=1.0)
    return trainer, compiled.as_text()


def test_blocked_drain_is_one_while_inside_one_loop_over_blocks(batched):
    """What takes a counter's place for the drain over blocks of lanes
    (PR 43): how often it engages is a fact of the compiled collector.
    At the batched-arrivals cell's 1024 lanes (its cluster, job axis
    and keys; a short scan) the collector compiled for the v5e holds
    ONE drain `while`, over 128 lanes, inside ONE loop over the eight
    blocks, under the drain's scope: one copy of the drain's program,
    not eight. PR 39's rule still holds inside a block: no instruction
    of that `while` yields an array of the adjacency's size."""
    from sparksched_tpu.trainers.rollout import _DRAIN_BLOCK

    trainer, text = batched
    p = trainer.params_env
    assert (trainer.num_envs, p.max_jobs, _DRAIN_BLOCK) == (1024, 20, 128)
    (over_blocks,) = _loops(text, "env/micro_step/drain/while")
    (drain,) = _loops(text, "env/micro_step/drain)/while")
    name = drain.split(" = ")[0].strip()
    assert any(name + " = " in body
               for body in _inside(text, over_blocks).values())
    # the outer loop carries the arrays at full width, the drain a
    # block of them
    assert "[1024," in over_blocks.split(" while(")[0]
    carried = drain.split(" while(")[0]
    assert "[128," in carried and "[1024," not in carried
    _holds_no_adjacency_sized_result(
        text, [_DRAIN_BLOCK, p.max_jobs, p.max_stages, p.max_stages],
        batch=trainer.num_envs)


# the fused bulk pass's early-exit loop, inside the vmapped drain
EARLY_EXIT = "vmap(env/micro_step/drain)/while/body/while/body"


def test_early_exit_loop_gathers_from_no_executor_count_table(batched):
    """What takes a counter's place for PR 47 in the collectors, whose
    bank is a constant of the program: the early-exit loop of the
    batched collector compiled for the v5e holds no gather from an
    operand of `num_executors + 1` elements. Until the sampler
    computed its executor-level interval from the executor count
    (`sampling.executor_interval`) each of the loop's two steps
    gathered from four loop-carried `s32[51]` tables and joined the
    rows: six of a step's 58 operations, the heaviest of
    `decima_batch20`'s `breakdown` among them. The loop still gathers
    (the bank's counts and durations), which shows that the pattern is
    found where it is."""
    trainer, text = batched
    n = trainer.params_env.num_executors
    in_loop = _gathers(text, EARLY_EXIT)
    assert len(in_loop) >= 6, in_loop
    assert not [op for _, op in in_loop if op == [n + 1]], in_loop


def _scalar_operands(text: str, scope: str) -> dict[str, int]:
    """The rank-0 operands of every fusion whose `op_name` holds
    `scope`, by the fusion's name (operands looked up by name in the
    fusion's own computation)."""
    import re

    found = {}
    for body in _computations(text).values():
        shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", body))
        for m in re.finditer(
            r"%([\w.\-]+) = [^\n]*? fusion\(([^)]*)\), kind=[^\n]*", body
        ):
            if scope in m.group(0):
                found[m.group(1)] = sum(
                    shapes.get(o, "").endswith("[]")
                    for o in re.findall(r"%([\w.\-]+)", m.group(2))
                )
    return found


@pytest.fixture(scope="module")
def fair_chunk(flagship, one_chip):
    """`sweep_chunk` under `config/sweep_fair_demo.yaml` (10 executors,
    50 jobs, the fair policy) at 2,048 lanes, compiled for the v5e
    with the bank as shapes (it is an ARGUMENT of the chunk program:
    the benchmark runs the timed executable over a second bank), once
    for the tests that read it: `(params, bank, HLO text)`."""
    import jax

    from sparksched_tpu import config, sweep

    jax.config.update("jax_default_prng_impl", "threefry2x32")
    try:  # the sweep's own keys; the flagship fixture's back after
        params, bank, scheduler = sweep.from_config(
            config.load("config/sweep_fair_demo.yaml"))
        key = jax.random.PRNGKey(0)
        carry = jax.eval_shape(
            lambda k: sweep.init(params, bank, k, 2048), key)
        compiled = sweep.sweep_chunk.lower(
            params, _on(one_chip, bank), scheduler.batch_policy,
            _on(one_chip, carry), _on(one_chip, key), 16,
        ).compile()
    finally:
        jax.config.update("jax_default_prng_impl", "rbg")
    assert (params.num_executors, params.max_jobs) == (10, 50)
    _fits(compiled, temp_gib=2.0)
    return params, bank, compiled.as_text()


def test_sweep_chunk_carries_no_table_as_scalars(fair_chunk):
    """What takes a counter's place for PR 47 in the sweep, whose bank
    is an ARGUMENT of the chunk program: the chunk compiled for the
    v5e has under the drain's scope no fusion with more than 8
    rank-0 operands. With the interval tables in the bank, `i32[11]`
    and so short that the compiler unrolled each gather into a chain
    of selects, three fusions took the 4 x 11 entries as 44 scalars
    carried through the drain's loops: two of them in every iteration
    of the early-exit loop, a fifth of `sweep_fair`'s device time."""
    _, _, text = fair_chunk
    assert len(_loops(text, "env/micro_step/drain/while")) == 1  # blocked
    scalars = _scalar_operands(text, "env/micro_step/drain")
    assert len(scalars) > 200, len(scalars)
    assert max(scalars.values()) <= 8, {
        k: v for k, v in scalars.items() if v > 8}
    # the early-exit loop is among what was read
    assert any(EARLY_EXIT in line and " fusion(" in line
               for line in text.split("\n"))


@pytest.mark.parametrize("program", ["sweep chunk", "batched collector"])
def test_early_exit_loop_gathers_three_bank_elements_a_step(
    request, program
):
    """What takes a counter's place for PR 50 (every sampled duration
    goes through it), in the programs as compiled for the v5e: the
    early-exit loop of the sweep chunk (bank an argument) and of the
    batched collector (bank a constant) holds SIX gathers, three a
    step of its two: one element each of the bank's `cnt`, `dur` and
    `rough_duration`. The parent's held eighteen: besides these (the
    `cnt` read three elements and a fourth gather picked one), the
    bank's `level_present` and `max_present`, and the lane's own
    `rem[tj, ts]`, `jcnt[tj]` and `job_template[tj]`, each a
    serialised `kCustom` fusion of 1.5 to 2.2 us with a relayout of
    its index column before it (the ten heaviest operations of
    `sweep_fair`'s `breakdown` were ten of them). What a step reads
    of the lane's own state it now picks with the one-hots it builds
    for its updates, the stage's word of `EnvState.duration_facts`
    among it, and nothing in the loop has `level_present`'s or the
    state's `[lanes, J, S]` dimensions as a gather's operand."""
    if program == "sweep chunk":
        params, bank, text = request.getfixturevalue("fair_chunk")
    else:
        trainer, text = request.getfixturevalue("batched")
        params, bank = trainer.params_env, trainer.bank
    in_loop = sorted(op for _, op in _gathers(text, EARLY_EXIT))
    tables = sorted(
        list(leaf.shape)
        for leaf in (bank.cnt, bank.dur, bank.rough_duration)
        for _ in range(2)
    )
    assert in_loop == tables, in_loop
    assert list(bank.level_present.shape) not in in_loop
    # the decide step (`_bulk_fulfill`) still gathers from the state's
    # grids, at the batch's full width: the pattern is found where it
    # is (until PR 51 the drain's body outside that loop did too)
    grid = [params.max_jobs, params.max_stages]
    decide = [op[1:] for _, op in _gathers(text, "env/micro_step/decide")]
    assert decide.count(grid) >= 4, decide


# the vmapped drain's `while` body, which holds the early-exit loop
DRAIN_BODY = "vmap(env/micro_step/drain)/while/body"


@pytest.mark.parametrize(
    "program", ["sweep chunk", "batched collector", "flagship collector"])
def test_drain_body_gathers_from_no_array_of_the_lane_s_own(
    request, program
):
    """What takes a counter's place for PR 51 (the mechanism engages in
    every drain body by construction), in the programs as compiled for
    the v5e: the drain body OUTSIDE the early-exit loop (the pop, the
    handlers, `_resolve_action`, `_apply_action`, `_refresh_sat`, the
    shared tail) holds THREE gathers, one element each of the bank's
    `cnt`, `dur` and `rough_duration` for `_apply_action`'s sampled
    duration, and none whose operand is a lane's own `[128,J,S]`,
    `[128,J]`, `[128,N]` or `[128,J,S,S]` array. The parent's held
    SEVENTY-ONE there (`s32[128,J,S]` fifteen times, `s32[128,N]`
    twenty, `pred[128,J,S]` fourteen, six rows of the adjacency): each
    a serialised `kCustom` fusion with a relayout of its index column
    before it. What the body reads of a lane's state at one (job,
    stage), job, executor or slot it picks with the one-hot its
    masked writes use (`core._pick`), and the adjacency's rows it
    reads off the packed parent sets (`core._job_parent_sets`): no
    adjacency gather stays. Under the drain's whole scope (the
    early-exit loop's six, and in the sweep the re-seed inside the
    loop over blocks) every gather's operand is a table of the bank."""
    if program == "sweep chunk":
        params, bank, text = request.getfixturevalue("fair_chunk")
    elif program == "batched collector":
        trainer, text = request.getfixturevalue("batched")
        params, bank = trainer.params_env, trainer.bank
    else:
        trainer = request.getfixturevalue("flagship")
        text = request.getfixturevalue("collector").as_text()
        params, bank = trainer.params_env, trainer.bank
    lanes = 128  # a block of the drain; the flagship's whole batch
    body = [op for _, op in _gathers(text, DRAIN_BODY)]
    in_loop = [op for _, op in _gathers(text, EARLY_EXIT)]
    tables = sorted(
        list(leaf.shape)
        for leaf in (bank.cnt, bank.dur, bank.rough_duration))
    assert len(in_loop) == 6 and len(body) == 9, (in_loop, body)
    fixed = sorted(body)
    for op in in_loop:
        fixed.remove(op)
    assert fixed == tables, fixed
    templates = bank.num_stages.shape[0]
    drain = [op for _, op in _gathers(text, "env/micro_step/drain")]
    assert len(drain) >= 9 and all(
        op[0] == templates and op[0] != lanes for op in drain), drain


def test_sweep_chunk_under_decima_is_one_net_in_the_loop_over_blocks(
    flagship, one_chip
):
    """PR 49: `sweep_chunk` under `config/sweep_decima_demo.yaml` (10
    executors, 50 jobs, the Decima net sampled, its weights an
    ARGUMENT) at 2,048 lanes compiles for the v5e and fits with room:
    the compiled program holds the net's level loop ONCE, inside the
    loop over blocks inside the rows' loop, beside the one drain
    `while`; its temporaries stay under a tenth of what one evaluation
    over all 2,048 lanes' [50, 20, 20] adjacencies and activations
    would take; and nothing of the weights is a constant of the
    program."""
    import jax

    from sparksched_tpu import config, sweep

    lanes = 2048
    jax.config.update("jax_default_prng_impl", "threefry2x32")
    try:  # the sweep's own keys; the flagship fixture's back after
        params, bank, scheduler = sweep.from_config(
            config.load("config/sweep_decima_demo.yaml"))
        key = jax.random.PRNGKey(0)
        carry = jax.eval_shape(
            lambda k: sweep.init(params, bank, k, lanes), key)
        compiled = sweep.sweep_chunk.lower(
            params, _on(one_chip, bank), scheduler.batch_policy,
            _on(one_chip, carry), _on(one_chip, key), 16,
            _on(one_chip, scheduler.params),
        ).compile()
    finally:
        jax.config.update("jax_default_prng_impl", "rbg")
    assert (params.num_executors, params.max_jobs) == (10, 50)
    _fits(compiled, temp_gib=0.6)
    text = compiled.as_text()
    rows, blocks = "jit(_chunk)/while", "/body/closed_call/while"
    levels = _loops(text, "decima/gnn/levels/while")
    assert len(levels) == 1
    assert f'op_name="{rows}{blocks}/body/closed_call/sweep/policy/' in (
        levels[0])
    assert len(_loops(text, rows)) == len(_loops(text, rows + blocks)) == 1
    assert len(_loops(text, "env/micro_step/drain)/while")) == 1
    # the weights come in as arguments: no constant of a Dense kernel's
    # size ([64, 64] float32 is the largest)
    assert "f32[64,64]" in text.split("ENTRY")[1].split("\n")[0]
    assert not [line for line in text.split("\n")
                if " constant(" in line and "f32[64,64]" in line]


def test_blocked_drain_leaves_the_sampler_its_layout(
    flagship, one_chip, tmp_path
):
    """What `decima_rollout_dp4`'s mesh check rests on besides the
    blocks (PR 43): the one-chip collector at the cell's 512 lanes x
    200 jobs drains four blocks, and the arrays around the drain keep
    the layout they have without it. With the blocks stacked and
    unstacked (`lax.map`) the carry's `[512,200,20]` grids came back
    job-major, `{2,0,1}`, the layout reached the sampler, its softmax
    over a lane's stage scores summed in another order, and a tenth of
    the log-probs parted from the mesh's by a last bit. Sliced out and
    written back in place they keep ONE layout, the one the mesh's
    program gives a chip's 128 lanes: `{2,1,0}`, lanes outermost,
    while the drain's gathers wanted a stage-minor carry; `{0,1,2}`,
    lane-minor, since PR 51 took them out (the mesh's program and this
    one compiled for the described v5e:2x2 agree on it, on both sides
    of that PR: PERF.md section 6, PR 51)."""
    import re

    import chip_smoke
    from sparksched_tpu.trainers import make_trainer

    cfg = chip_smoke.load_cfg(
        "config/decima_tpch_multichip.yaml", str(tmp_path))
    cfg["parallel"] = {"dp": 1}
    cfg["trainer"] |= {
        "num_sequences": 64, "num_rollouts": 8, "rollout_steps": 16}
    trainer = make_trainer(cfg)
    p = trainer.params_env
    assert (trainer.num_envs, p.max_jobs, p.max_stages) == (512, 200, 20)
    state = _train_state(trainer, one_chip)
    text = trainer._collect_jit.lower(
        state.params, state.iteration, state.rng, None).compile().as_text()
    assert len(_loops(text, "env/micro_step/drain/while")) == 1  # blocked
    grids = re.findall(
        r"= f32\[512,200,20\]\{([\d,]+)[^\n]*decima/sample", text)
    assert len(grids) >= 4 and set(grids) == {"0,1,2"}, grids


@pytest.mark.parametrize("batched", [False, True])
def test_serve_programs_compile(flagship, one_chip, batched):
    """`serve_decide` and `serve_decide_batch` as `SessionStore` builds
    them, at the smoke run's store shape (hot set 128, K=8, donated)."""
    import jax
    import jax.numpy as jnp

    import chip_smoke
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.serve.aot import (
        SERVE_KNOBS,
        serve_decide_batch_fn,
        serve_decide_fn,
    )

    p, bank = flagship.params_env, flagship.bank
    hot = chip_smoke.SERVE_CFG["hot_capacity"]
    k = chip_smoke.SERVE_CFG["max_batch"]
    pol, bpol = flagship.scheduler.serve_param_policies(deterministic=True)
    state = _train_state(flagship, one_chip)
    slot = jax.eval_shape(
        lambda key: init_loop_state(core.reset(p, bank, key)),
        jax.random.PRNGKey(0),
    )
    store = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(
            (hot,) + tuple(l.shape), l.dtype, sharding=one_chip
        ),
        slot,
    )
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    flag = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)
    slots = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    if batched:
        fn = serve_decide_batch_fn(p, bank, bpol, k, SERVE_KNOBS)
        args = (store, state.params, slots, state.rng)
    else:
        fn = serve_decide_fn(p, bank, pol, SERVE_KNOBS)
        args = (store, state.params, i32, state.rng, i32, i32, flag)
    _fits(jax.jit(fn, donate_argnums=(0,)).lower(*args).compile())


@pytest.mark.parametrize("sub_batch", [1024, 512])
def test_flat_chunk_1024_lanes_compiles(flagship, one_chip, sub_batch):
    """bench.py's flat micro-step chunk at the headline 1024 lanes,
    unsplit and in the 512-lane sub-batches an old fault made the
    default: the installed compiler takes both."""
    import jax

    import bench
    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like
    from sparksched_tpu.workload import make_workload_bank

    p = EnvParams(
        num_executors=10, max_jobs=50, max_stages=20, max_levels=20,
        moving_delay=2000.0, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
    )
    bank = make_workload_bank(p.num_executors, p.max_stages)
    p = p.replace(max_stages=bank.max_stages, max_levels=bank.max_stages)
    n = 1024
    keys = jax.eval_shape(
        lambda k: jax.random.split(k, n), jax.random.PRNGKey(0)
    )
    lanes = jax.eval_shape(
        lambda ks: jax.vmap(
            lambda k: init_loop_state(core.reset(p, bank, k))
        )(ks),
        keys,
    )
    telem = jax.eval_shape(lambda: telemetry_zeros_like((n,)))
    _fits(bench.bench_chunk.lower(
        p, _on(one_chip, bank), _on(one_chip, lanes),
        _on(one_chip, keys), 8, True, 1, _on(one_chip, telem),
        sub_batch=sub_batch,
    ).compile())
