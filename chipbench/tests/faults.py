"""Faults planted in the program underneath the timed path."""
import jax


def unchanged_state(ctl):
    """The train step hands back the state it was given."""
    eng = ctl.engine
    step = eng._train

    def train(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    eng._train = train


def half_batch(ctl):
    """The train step leaves out half of the batch and takes the mean
    over the rest."""
    eng = ctl.engine
    step = eng._train

    def train(state, batch):
        return step(state, jax.tree.map(lambda x: x[: len(x) // 2], batch))
    eng._train = train


def served_token(ctl):
    """Every decoded token is replaced by its successor as the decode
    tick produces it."""
    plane = ctl.serve_plane
    decode = plane._fleet_decode
    vocab = ctl.engine.cfg.vocab_size

    def tick(*a):
        nxt, cache = decode(*a)
        return (nxt + 1) % vocab, cache
    plane._fleet_decode = tick


def eval_answer(ctl):
    """Each batched eval reports a tenth more accuracy than it measured."""
    eng = ctl.engine
    flat_fn = eng._acc_flat_fn

    def acc_flat_fn(b, precision="fp32"):
        fn = flat_fn(b, precision)
        return lambda params, toks: fn(params, toks) + 0.1
    eng._acc_flat_fn = acc_flat_fn


def drift_score(ctl):
    """The drift plane stores each score a hundredth too high."""
    fleet = ctl.fleet
    observe = fleet.observe

    def obs(ids, toks):
        out = observe(ids, toks)
        fleet._scores[:len(ids)] += 0.01
        return out
    fleet.observe = obs


def partial_fleet(ctl):
    """The drift plane scores only the first half of the cameras it is
    handed."""
    fleet = ctl.fleet
    observe = fleet.observe

    def obs(ids, toks):
        half = max(1, len(ids) // 2)
        return observe(ids[:half], toks[:half])
    fleet.observe = obs


ALL = {"unchanged_state": unchanged_state, "half_batch": half_batch,
       "served_token": served_token, "eval_answer": eval_answer,
       "drift_score": drift_score, "partial_fleet": partial_fleet}
