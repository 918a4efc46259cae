"""``python -m tools.dslint --explain DS0NN`` — print one rule's
documentation plus a minimal true-positive example.

The examples double as living documentation of what each rule actually
fires on: every snippet here is the smallest program that trips its
rule, written in the repo's own idiom. (They are illustrative text, not
fixtures — the executable fixtures live in tests/.)
"""

from typing import Dict, Optional

EXAMPLES: Dict[str, str] = {
    "DS001": """\
x = jnp.zeros((4, 4))
for i in range(4):
    x = x.at[i].set(i)          # DS001: per-element .at[] in a python
                                # loop — one dispatch per element""",
    "DS002": """\
step = jax.jit(lambda p, x, flag: p * x if flag else x)
# DS002: `flag` selects a branch but is not in static_argnums/names""",
    "DS003": """\
step = jax.jit(update, donate_argnums=(0,))
new = step(params, grads)
loss = compute(params)          # DS003: `params` used after donation""",
    "DS004": """\
@partial(jax.jit)
def f(x):
    if x > 0:                   # DS004: python branch on a traced value
        return x
    return -x""",
    "DS005": """\
def choose_impl():
    return os.environ.get("DS_ATTN_IMPL", "gather")
# DS005: env read outside utils/env.py's registered-flag layer""",
    "DS006": """\
result = jax.device_get(x)
y = compute(result)
z = jax.device_get(y)           # DS006: sync inside the hot loop""",
    "DS007": """\
@partial(jax.jit)
def f(x):
    print("tracing", x)         # DS007: host side effect under trace""",
    "DS008": """\
pool = jnp.zeros((L, N, B, H, D))
pool2 = pool + 0                # DS008: whole-pool copy on the serving
                                # path — doubles HBM transiently""",
    "DS009": """\
def step(self, tokens):
    return self._decode(np.asarray(tokens))
# DS009: host array fed straight to a jitted call per step —
# re-uploads every dispatch""",
    "DS010": """\
key = jax.random.PRNGKey(0)
for _ in range(n):
    tok = sample(key)           # DS010: key reused — identical draws""",
    "DS011": """\
step = jax.jit(update, donate_argnums=(0,))   # donates params


def caller(params, grads):
    new = step(params, grads)
    return params, new          # DS011: caller keeps the donated ref""",
    "DS012": """\
def cow(self, src, dst):
    # fault site "cache.cow" is in FAULT_SITES but no maybe_fire
    # ever names it on this path  -> DS012 (integrity direction)
    return self._cow_blocks(src, dst)""",
    "DS013": """\
impl = os.environ.get("DS_NEW_KNOB")   # DS013: flag read but never
                                       # declared in utils/env.py""",
    "DS014": """\
self._m = Counter("serving_new_metric")   # DS014: registered metric
# missing from tools/dslint/telemetry_schema.json""",
    "DS016": """\
def admit(self, rid, n):
    slot = self.cache.allocate(rid, n)
    if self.adapters is not None:
        row = self.adapters.acquire(rid)    # may raise
        # DS016: on the exception edge out of acquire(), `slot`
        # reaches function exit without cache.free(slot) — leaked
    self.slots[rid] = slot""",
    "DS017": """\
@partial(jax.jit)
def f(x):
    y = x * 2
    flag = y.sum()
    if flag > 0:                # DS017: branch on `flag`, which derives
        return y                # from traced `x` via assignments —
    return -y                   # DS004 can't see through the chain""",
    "DS018": """\
@dataclass
class ServeRequest:
    rid: str
    retries: int = 0            # DS018: written by the scheduler but
                                # absent from snapshot_entry() and not
                                # declared in SNAPSHOT_EPHEMERAL


def snapshot_entry(req):
    return {"rid": req.rid}""",
}


def explain(rule_id: str) -> Optional[str]:
    """Formatted doc + minimal TP example for one rule id, or None when
    the id is unknown."""
    from tools.dslint.interproc import interproc_catalog
    from tools.dslint.rules import rule_catalog
    rule_id = rule_id.strip().upper()
    entry = next((r for r in rule_catalog() + interproc_catalog()
                  if r["id"] == rule_id), None)
    if entry is None:
        return None
    fix = " [autofixable]" if entry["autofixable"] else ""
    lines = [f"{entry['id']} — {entry['name']}{fix}", "",
             entry["rationale"], ""]
    example = EXAMPLES.get(rule_id)
    if example:
        lines.append("minimal true positive:")
        lines.append("")
        lines.extend("    " + l for l in example.splitlines())
        lines.append("")
    lines.append(f"docs: docs/LINT.md; suppress with "
                 f"`# dslint: disable={rule_id} — <reason>`")
    return "\n".join(lines)
