"""What the files that compile for a described TPU v5e share (not a test
file): the one-chip sharding of a described v5e:2x2 and the readers of a
compiled text.

The topology is described inside a fixture, never at import: the xdist
workers import every test file, and each describes its own when a test of
its files asks (``ALLOW_MULTIPLE_LIBTPU_LOAD`` in tier-1's command lets
them load libtpu side by side)."""

import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(autouse=True, scope="module")
def described_chips_programs_stay_out_of_the_cache():
    """An executable compiled for a DESCRIBED chip cannot be loaded back
    (``DeserializeLoadedExecutable`` is unimplemented without the device):
    an entry of the tests' persistent cache would cost its megabytes and
    answer the next run with a warning and the same compile. No program
    these files compile is written (the threshold is read at each write)."""
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    yield
    jax.config.update(name, was)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _mosaic_call_names(hlo):
    """Names of the compiled text's Mosaic calls, XLA's numbering cut."""
    return [re.sub(r"\.\d+$", "", name) for name in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)]


def _xla_remats(hlo):
    """Names of the instructions XLA's OWN rematerialisation added to the
    compiled text (``%fusion.790.remat``, ``%gte.remat.3``): what the
    compiler computes a second time to stay under the chip's memory, apart
    from what the program's ``jax.checkpoint`` asked for."""
    return re.findall(r"%([\w.\-]+\.remat(?:\.\d+)?) = ", hlo)


def _mosaic_grids(hlo, name):
    """The grid (Mosaic's ``iteration_bounds``) of every Mosaic call
    ``name`` in the compiled text, read out of the call's own module: the
    bytecode its ``backend_config`` carries."""
    import base64
    import json

    from jax._src.lib.mlir import ir
    grids = []
    for line in hlo.splitlines():
        if not re.match(r"\s*(ROOT )?%%%s(\.\d+)? = [^\n]*\"tpu_custom_call\""
                        % re.escape(name), line):
            continue
        config = json.loads(re.search(r"backend_config=(\{.*\})",
                                      line).group(1))
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(base64.b64decode(
                config["custom_call_config"]["body"])).operation.get_asm()
        bounds, = re.findall(r"iteration_bounds = array<i64: ([^>]*)>", asm)
        grids.append(tuple(int(n) for n in bounds.split(",")))
    return grids


def _scoped_vmem(hlo, name):
    """(asked, used) bytes of scoped VMEM of every Mosaic call ``name`` in
    the compiled text: the call's ``vmem_limit_bytes`` and what Mosaic
    laid out under it."""
    size = r'scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"'
    calls = [line for line in hlo.splitlines() if re.match(
        r"\s*(ROOT )?%%%s[\w\-.]* = [^\n]*\"tpu_custom_call\"" % name, line)]
    return [(int(re.search('"' + size, line).group(1)),
             int(re.search('"used_' + size, line).group(1)))
            for line in calls]


def _conv_pool_moves(hlo, pool_rows):
    """XLA's gathers and scatters (and copies) of the pool of carried
    convolution inputs, whose slots are ``pool_rows`` rows of 128 lanes, in
    the compiled text."""
    shaped = r"bf16\[\d+,\d+,%d,128\]" % pool_rows
    return [line.strip()[:120] for line in hlo.splitlines()
            if re.search(shaped, line)
            and re.search(r" (gather|scatter|copy)\(", line)]
