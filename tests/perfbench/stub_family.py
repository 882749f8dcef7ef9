"""A second model family made of files under tests/perfbench/ alone, to show
that the harness's seam is whole: a configuration whose `family` is "stub"
goes from set-up to a result's line with nothing under perfbench/ patched
(test_perfbench_families.py registers this module as
perfbench.families.stub).

Nothing here is the Llama's shape: the model is one recurrent layer with a
state of fixed size a request and no cache (stub_reference.py); its engine
and its train step are fakes in numpy and plain JAX that keep the program's
calling conventions; and its arithmetic counts two kinds of layer, as a
hybrid of linear-attention and full-attention layers would: `state` layers
read and write a fixed state a request once a step whatever the context,
`attn` layers hold a cache that grows a row a token and are the only ones
that call the attention kernel."""
import numpy as np

CFG = {"name": "stub-hybrid", "family": "stub", "dtype": "float32",
       "vocab_size": 256, "hidden_size": 32,
       "layer_kinds": ["state", "state", "state", "attn"],
       "state_bytes": 4096, "row_bytes": 64, "heads": 4, "head_dim": 8}
HELD = []           # what the runner asked held_bytes for

# ------------------------------------------------------------- the program

GAINS = ("gain",)


def shapes(cfg):
    V, D = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": (V, D), "mix": (1, D), "gain": (D,), "head": (D, V)}


class Request:
    def __init__(self, rid, prompt, n_out):
        self.rid, self.prompt, self.n_out = rid, list(prompt), n_out
        self.out, self.done, self.reason, self.h = [], False, None, None


class Engine:
    """A fake of the serving engine's calling conventions: a queue, slots,
    one prefill a step for each free slot, then `burst` decode steps."""

    def __init__(self, cfg, settings, weights):
        self.w = {k: np.asarray(v, np.float32) for k, v in weights.items()}
        self.a = 1.0 / (1.0 + np.exp(-self.w["mix"][0]))
        self.max_batch, self.burst = settings["max_batch"], settings["burst"]
        self._queue, self._slots, self._finished = [], [], []
        self._page_buckets, self._rid = (), 0
        self.stats = {"bursts": 0, "decode_steps": 0, "prefills": 0,
                      "preemptions": 0, "admission_stalls": 0,
                      "page_buckets_used": []}

    @property
    def pending(self):
        return len(self._queue) + len(self._slots)

    def add_request(self, prompt, max_new_tokens):
        self._rid += 1
        self._queue.append(Request(self._rid, prompt, max_new_tokens))
        return self._rid

    def _emit(self, req, token):
        req.h = self.a * req.h + self.w["embed"][token]
        req.out.append(int(np.argmax((req.h * self.w["gain"])
                                     @ self.w["head"])))
        if len(req.out) >= req.n_out:
            req.done, req.reason = True, "complete"

    def step(self):
        while self._queue and len(self._slots) < self.max_batch:
            req = self._queue.pop(0)
            req.h = np.zeros_like(self.a)
            for token in req.prompt[:-1]:
                req.h = self.a * req.h + self.w["embed"][token]
            self._emit(req, req.prompt[-1])
            self._slots.append(req)
            self.stats["prefills"] += 1
        for _ in range(self.burst):
            live = [r for r in self._slots if not r.done]
            if not live:
                break
            for req in live:
                self._emit(req, req.out[-1])
            self.stats["decode_steps"] += 1
        self.stats["bursts"] += 1
        self._finished += [r for r in self._slots if r.done]
        self._slots = [r for r in self._slots if not r.done]

    def run(self):
        while self.pending:
            self.step()

    def take_finished(self):
        done, self._finished = self._finished, []
        return done


def engine(cfg, traffic, weights):
    return Engine(cfg, traffic["engine"], weights)


class TrainStep:
    """A fake of the train step's calling conventions: called on a batch it
    returns the loss and has moved `params` by one AdamW update."""

    def __init__(self, job, params):
        import jax.numpy as jnp
        o = job["optimizer"]
        self.hp = (o["lr"], o["beta1"], o["beta2"], o["eps"],
                   o["weight_decay"])
        self.params, self.t = params, 0
        self.m = {k: jnp.zeros_like(v) for k, v in params.items()}
        self.v = dict(self.m)

    def __call__(self, tokens, labels):
        import jax.numpy as jnp
        lr, b1, b2, eps, wd = self.hp
        loss, g = reference().loss_and_grads(
            self.params, jnp.asarray(tokens), jnp.asarray(labels), cfg=(),
            dot="f32")
        self.t += 1
        for k, p in self.params.items():
            self.m[k] = b1 * self.m[k] + (1 - b1) * g[k]
            self.v[k] = b2 * self.v[k] + (1 - b2) * g[k] * g[k]
            self.params[k] = p - lr * (self.m[k] / (1 - b1 ** self.t)) / (
                jnp.sqrt(self.v[k] / (1 - b2 ** self.t)) + eps) - lr * wd * p
        return loss

    def resilience_state(self):
        return {"params": self.params, "step": self.t,
                "opt_state": {k: {"moment1": m} for k, m in self.m.items()}}


def train_step(cfg, job, mesh, make_weights):
    return TrainStep(job, dict(make_weights()))


# ----------------------------------------------------------- the reference

def reference():
    import stub_reference
    return stub_reference


def layer_axes(name, ndim):
    return (1,) if name == "mix" else None


# ---------------------------------------------------------------- the work

def _kinds(cfg):
    kinds = cfg["layer_kinds"]
    return kinds.count("state"), kinds.count("attn")


def _weight_bytes(cfg):
    return 4 * sum(int(np.prod(s)) for s in shapes(cfg).values())


def prefill_work(cfg, tlen):
    n_state, n_attn = _kinds(cfg)
    flops = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * tlen \
        + 2.0 * n_attn * cfg["hidden_size"] * tlen * tlen
    return flops, (_weight_bytes(cfg) + n_state * cfg["state_bytes"]
                   + n_attn * cfg["row_bytes"] * tlen)


def burst_work(cfg, decode_steps, decodes):
    n_state, n_attn = _kinds(cfg)
    tokens = sum(n for _, n in decodes)
    rows = sum(n * (c + 1) + n * (n - 1) // 2 + n for c, n in decodes)
    flops = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * tokens \
        + 4.0 * n_attn * cfg["hidden_size"] * (rows - tokens)
    return flops, (decode_steps * _weight_bytes(cfg)
                   + tokens * 2 * n_state * cfg["state_bytes"]
                   + rows * n_attn * cfg["row_bytes"])


def train_flops_per_token(cfg, seq_len):
    return 6.0 * cfg["hidden_size"] * cfg["vocab_size"] \
        + 6.0 * _kinds(cfg)[1] * cfg["hidden_size"] * seq_len


def held_bytes(cfg, live_rows, n_live):
    n_state, n_attn = _kinds(cfg)
    HELD.append((live_rows, n_live))
    return n_live * n_state * cfg["state_bytes"] \
        + live_rows * n_attn * cfg["row_bytes"]


def train_attention_calls(cfg, batch, seq_len):
    return [((batch, cfg["heads"], cfg["heads"], seq_len, cfg["head_dim"]),
             _kinds(cfg)[1])]
