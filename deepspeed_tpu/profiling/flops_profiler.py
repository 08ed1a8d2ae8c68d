"""FLOPS profiler — jaxpr/XLA cost analysis instead of module hooks.

Analogue of the reference ``profiling/flops_profiler/profiler.py:30``
(``FlopsProfiler``): the reference monkey-patches ``torch.nn.functional`` to
count MACs per module; on TPU the compiler already knows — XLA's
``cost_analysis()`` gives whole-program flops/bytes, and walking the jaxpr
gives the per-primitive breakdown (the "module depth" of a functional
program). The reference's printed-profile surface (total flops/params/
duration, top items, optional file output) is preserved.
"""

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from deepspeed_tpu.utils.logging import log_dist, logger


def _dot_flops(eqn) -> float:
    """2*M*N*K flops for a dot_general from its shapes."""
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    batch = 1.0
    for d in lb:
        batch *= a.shape[d]
    k = 1.0
    for d in lc:
        k *= a.shape[d]
    m = 1.0
    for i, d in enumerate(a.shape):
        if i not in lc and i not in lb:
            m *= d
    n = 1.0
    for i, d in enumerate(b.shape):
        if i not in rc and i not in rb:
            n *= d
    return 2.0 * batch * m * n * k


def jaxpr_flops_by_primitive(jaxpr, scale: float = 1.0) -> Dict[str, float]:
    """Recursively aggregate matmul flops + op counts per primitive. Scans
    multiply their body by the trip count; inner jaxprs (pjit/remat/custom
    vjp) recurse at the same scale."""
    out: Dict[str, float] = {}

    def add(name, val):
        out[name] = out.get(name, 0.0) + val

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            add("dot_general", _dot_flops(eqn) * scale)
            continue
        if prim == "scan":
            inner = jaxpr_flops_by_primitive(
                eqn.params["jaxpr"].jaxpr, scale * eqn.params["length"]
            )
            for k, v in inner.items():
                add(k, v)
            continue
        sub = None
        for key in ("jaxpr", "call_jaxpr"):
            if key in eqn.params:
                sub = eqn.params[key]
                break
        if sub is not None:
            sub_jaxpr = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            for k, v in jaxpr_flops_by_primitive(sub_jaxpr, scale).items():
                add(k, v)
            continue
        if prim == "while":
            # trip count is dynamic: count ONE body iteration (a lower bound)
            # and surface the loop marker so readers know it's per-iteration
            body = eqn.params.get("body_jaxpr")
            if body is not None:
                for k, v in jaxpr_flops_by_primitive(body.jaxpr, scale).items():
                    add(k if k.startswith("#") else f"{k}(per while iter)", v)
            add("#while", scale)
            continue
        if prim == "cond":
            # one branch executes: take the max (upper bound), not the sum
            branch_costs = [
                jaxpr_flops_by_primitive(br.jaxpr, scale)
                for br in eqn.params.get("branches", ())
            ]
            keys = {k for bc in branch_costs for k in bc}
            for k in keys:
                add(k, max(bc.get(k, 0.0) for bc in branch_costs))
            continue
        # non-matmul primitive: count invocations (elementwise/collective mix)
        add(f"#{prim}", scale)
    return out


def analyze_fn(fn: Callable, *args, **kwargs) -> Dict[str, Any]:
    """Lower ``fn`` and return {'flops', 'bytes_accessed', 'optimal_seconds',
    'by_primitive'} — flops/bytes from XLA's own cost model, breakdown from
    the jaxpr."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    cost = lowered.compile().cost_analysis() or {}
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "optimal_seconds": float(cost.get("optimal_seconds", 0.0)),
        "by_primitive": jaxpr_flops_by_primitive(jaxpr.jaxpr),
    }


def num_to_string(num: float, precision: int = 2) -> str:
    if num >= 1e12:
        return f"{num / 1e12:.{precision}f} T"
    if num >= 1e9:
        return f"{num / 1e9:.{precision}f} G"
    if num >= 1e6:
        return f"{num / 1e6:.{precision}f} M"
    if num >= 1e3:
        return f"{num / 1e3:.{precision}f} K"
    return f"{num:.{precision}f}"


class FlopsProfiler:
    """Reference-API profiler over a jax step function.

    Typical flow (mirrors profiler.py usage):
        prof = FlopsProfiler()
        prof.start_profile()
        out = step_fn(*args)            # one profiled execution
        prof.stop_profile(step_fn, *args)
        prof.print_model_profile()
        prof.end_profile()
    The engine drives this automatically at ``flops_profiler.profile_step``.
    """

    def __init__(self, model: Optional[Callable] = None, ds_engine=None):
        self.model = model
        self.ds_engine = ds_engine
        self.started = False
        self._t0 = 0.0
        self._duration = 0.0
        self._analysis: Dict[str, Any] = {}
        self._n_params = 0

    def start_profile(self, ignore_list=None):
        self.started = True
        self._t0 = time.perf_counter()

    def stop_profile(self, fn: Optional[Callable] = None, *args, **kwargs):
        if not self.started:
            return
        self._duration = time.perf_counter() - self._t0
        if fn is not None:
            self._analysis = analyze_fn(fn, *args, **kwargs)

    def reset_profile(self):
        self._analysis = {}
        self._duration = 0.0

    def end_profile(self):
        self.started = False
        self.reset_profile()

    # -- reference getters --------------------------------------------------
    def get_total_flops(self, as_string: bool = False):
        f = self._analysis.get("flops", 0.0)
        return num_to_string(f) + "FLOPS" if as_string else f

    def get_total_macs(self, as_string: bool = False):
        m = self._analysis.get("flops", 0.0) / 2.0
        return num_to_string(m) + "MACs" if as_string else m

    def get_total_duration(self, as_string: bool = False):
        return f"{self._duration * 1e3:.2f} ms" if as_string else self._duration

    def set_total_params(self, params: Any):
        from deepspeed_tpu.models import num_params

        self._n_params = num_params(params)

    def get_total_params(self, as_string: bool = False):
        return num_to_string(self._n_params) if as_string else self._n_params

    def print_model_profile(
        self,
        profile_step: int = 1,
        module_depth: int = -1,
        top_modules: int = 1,
        detailed: bool = True,
        output_file: Optional[str] = None,
    ):
        lines = [
            "-" * 60,
            f"DeepSpeed-TPU Flops Profiler (step {profile_step})",
            "-" * 60,
            f"params:               {self.get_total_params(True)}",
            f"fwd+bwd+step flops:   {self.get_total_flops(True)}",
            f"bytes accessed:       {num_to_string(self._analysis.get('bytes_accessed', 0))}B",
            f"measured duration:    {self.get_total_duration(True)}",
        ]
        dur = self._duration
        if dur > 0 and self._analysis.get("flops"):
            lines.append(f"achieved:             {num_to_string(self._analysis['flops'] / dur)}FLOPS/s")
        if detailed and self._analysis.get("by_primitive"):
            lines.append("matmul flops by primitive / op counts:")
            items = sorted(
                self._analysis["by_primitive"].items(), key=lambda kv: -kv[1]
            )[: max(top_modules, 1)]
            for k, v in items:
                if k.startswith("#"):
                    lines.append(f"  {k:<28} x{int(v)}")
                else:
                    lines.append(f"  {k:<28} {num_to_string(v)}FLOPS")
        lines.append("-" * 60)
        text = "\n".join(lines)
        if output_file:
            if jax.process_index() == 0:  # one writer on shared filesystems
                with open(output_file, "w") as f:
                    f.write(text + "\n")
        else:
            log_dist(text, ranks=[0])
        return text
