"""Character-sequence datasets for RNN/LSTM models under drift.

The reference's sequence task is Shakespeare next-character prediction
(fedml_api/model/nlp/rnn.py:4-33, vocab 90, seq len 80) wired only into the
*non-drift* FedAvg pipeline. Here it composes with the drift pipeline like any
other dataset (BASELINE.md config 5 requires AUE over fed_shakespeare).

Hermetic generation: each concept is a distinct seeded Markov chain over the
character vocabulary; a drift changes the transition matrix, i.e. the language
statistics. Sequences are token-id arrays [seq_len] with the next character as
label — the same (x, y) contract as the reference's dataloader
(fed_shakespeare/utils.py::split: x = window[:-1], y = window[-1]).

Real data, when present under ``data_dir``, replaces synthesis:

- ``fed_shakespeare/datasets/shakespeare_train.h5`` — the TFF h5 layout
  (examples/<client>/snippets byte strings,
  reference fed_shakespeare/data_loader.py:20-56);
- ``shakespeare/train/*.json`` — the LEAF layout (users / user_data x,y
  sentence strings, reference shakespeare/data_loader.py:13-50);
- ``stackoverflow/datasets/stackoverflow_train.h5`` + ``.word_count`` —
  the TFF word-NWP layout (examples/<client>/tokens,
  reference stackoverflow_nwp/data_loader.py:18-45).

Concept drift on real text is an alphabet rotation: concept k serves the
same corpus with token ids rotated by a concept-specific offset. This is
the sequence analog of the reference's MNIST label-swap drift
(MNIST/data_loader_cont.py:179-214) — real content, changed symbol
semantics — chosen because the reference wires its text datasets only into
the non-drift pipeline and defines no text-drift transform of its own.
"""

from __future__ import annotations

import json
import os

import numpy as np

from feddrift_tpu.data.changepoints import concept_matrix
from feddrift_tpu.data.drift_dataset import DriftDataset

VOCAB_SIZE = 90   # reference rnn.py:18
SEQ_LEN = 80      # reference LEAF shakespeare sequence length. Default for
                  # DIRECT generate_text_drift callers only: the product
                  # path (data/registry.py) always passes
                  # ExperimentConfig.text_seq_len, whose default pins the
                  # same reference value.

# The TFF character vocabulary (fed_shakespeare/utils.py::CHAR_VOCAB, 86
# chars) plus the four structural slots (pad / bos / eos / oov) = 90 ids,
# matching the CharLSTM's embedding table (rnn.py:18). LEAF-JSON text is
# mapped through the same table (unknown chars -> oov) so both on-disk
# formats produce one id space.
CHAR_VOCAB = ('dhlptx@DHLPTX $(,048cgkoswCGKOSW[_#\'/37;?bfjnrvzBFJNRVZ"&*.26:'
              '\naeimquyAEIMQUY]!%)-159\r')
PAD_ID = 0
BOS_ID = len(CHAR_VOCAB) + 1    # 87
EOS_ID = len(CHAR_VOCAB) + 2    # 88
OOV_ID = len(CHAR_VOCAB) + 3    # 89
_CHAR_TO_ID = {ch: i + 1 for i, ch in enumerate(CHAR_VOCAB)}


def _char_ids(text: str) -> np.ndarray:
    return np.array([_CHAR_TO_ID.get(ch, OOV_ID) for ch in text], np.int32)


def load_word_ranks(path: str, k: int) -> list[str]:
    """Top-k words of a TFF ``word_count`` file ("word count" per line,
    frequency-ranked — the reference's get_most_frequent_words,
    stackoverflow_lr/utils.py:15-19). Shared by the NWP and LR loaders."""
    with open(path) as fh:
        return [ln.split()[0] for ln in fh if ln.strip()][:k]


def iter_tff_clients(h5file):
    """Yield the ``examples/<client>`` groups of a TFF-layout h5 in sorted
    client-key order (deterministic corpus identity across runs)."""
    for cid in sorted(h5file["examples"].keys()):
        yield h5file["examples"][cid]


# Window sampling only ever consumes C * (T+1) * sample_num windows, so a
# bounded prefix of a huge on-disk corpus (full TFF StackOverflow is ~1.7B
# tokens) gives identical coverage without materializing the whole stream.
_MAX_CORPUS_IDS = 2_000_000


def _try_load_char_corpus(data_dir: str, min_len: int,
                          max_len: int = _MAX_CORPUS_IDS) -> np.ndarray | None:
    """Real Shakespeare as one id stream, or None if no files are present."""
    h5path = os.path.join(data_dir, "fed_shakespeare", "datasets",
                          "shakespeare_train.h5")
    chunks: list[np.ndarray] = []
    total = 0
    if os.path.isfile(h5path):
        import h5py
        with h5py.File(h5path, "r") as f:
            for ex in iter_tff_clients(f):
                if total >= max_len:
                    break
                for snip in ex["snippets"][()]:
                    ids = _char_ids(snip.decode("utf8"))
                    chunks.append(np.concatenate(
                        [[BOS_ID], ids, [EOS_ID]]).astype(np.int32))
                    total += len(chunks[-1])
                    if total >= max_len:
                        break
    else:
        jdir = os.path.join(data_dir, "shakespeare", "train")
        if os.path.isdir(jdir):
            for fn in sorted(os.listdir(jdir)):
                if not fn.endswith(".json") or total >= max_len:
                    continue
                with open(os.path.join(jdir, fn)) as fh:
                    d = json.load(fh)
                for u in d["users"]:
                    if total >= max_len:
                        break
                    ud = d["user_data"][u]
                    for sent, nxt in zip(ud["x"], ud["y"]):
                        chunks.append(np.concatenate(
                            [_char_ids(sent + nxt), [EOS_ID]]).astype(np.int32))
                        total += len(chunks[-1])
    if not chunks:
        return None
    corpus = np.concatenate(chunks)[:max_len]
    return corpus if len(corpus) >= min_len else None


def _try_load_word_corpus(data_dir: str, vocab: int, min_len: int,
                          max_len: int = _MAX_CORPUS_IDS) -> np.ndarray | None:
    """Real StackOverflow token stream (TFF h5 + word_count vocab file)."""
    base = os.path.join(data_dir, "stackoverflow", "datasets")
    h5path = os.path.join(base, "stackoverflow_train.h5")
    wcpath = os.path.join(base, "stackoverflow.word_count")
    if not (os.path.isfile(h5path) and os.path.isfile(wcpath)):
        return None
    # word ids 1..vocab-2 by corpus frequency rank;
    # 0 is reserved (pad), vocab-1 is the oov bucket.
    word_id = {w: i + 1
               for i, w in enumerate(load_word_ranks(wcpath, vocab - 2))}
    import h5py
    ids: list[int] = []
    with h5py.File(h5path, "r") as f:
        for ex in iter_tff_clients(f):
            if len(ids) >= max_len:
                break
            for sent in ex["tokens"][()]:
                ids.extend(word_id.get(w, vocab - 1)
                           for w in sent.decode("utf8").split())
                if len(ids) >= max_len:
                    break
    if len(ids) < min_len:
        return None
    return np.asarray(ids[:max_len], np.int32)


def _real_text_windows(
    corpus: np.ndarray,
    concepts: np.ndarray,
    num_clients: int,
    sample_num: int,
    seq_len: int,
    vocab: int,
    rng: np.random.Generator,
    noise_prob: float,
    name: str,
) -> DriftDataset:
    """Serve (seq_len+1)-char windows of a real corpus; concept k rotates
    the alphabet (see module docstring)."""
    T1 = concepts.shape[0]
    x = np.zeros((num_clients, T1, sample_num, seq_len), np.int32)
    y = np.zeros((num_clients, T1, sample_num), np.int32)
    for t in range(T1):
        for c in range(num_clients):
            k = int(concepts[t, c])
            # valid starts: 0 .. len-seq_len-1 inclusive (window is
            # seq_len+1 ids); integers() high bound is exclusive
            starts = rng.integers(0, len(corpus) - seq_len,
                                  size=sample_num)
            win = corpus[starts[:, None] + np.arange(seq_len + 1)]
            if k:
                win = (win + 31 * k) % vocab
            x[c, t] = win[:, :seq_len]
            ys = win[:, seq_len].copy()
            if noise_prob > 0:
                flip = rng.random(sample_num) < noise_prob
                ys = np.where(flip, rng.integers(0, vocab, size=sample_num),
                              ys).astype(np.int32)
            y[c, t] = ys
    return DriftDataset(x=x, y=y, num_classes=vocab, concepts=concepts,
                        name=name, is_sequence=True,
                        meta={"vocab": vocab, "seq_len": seq_len,
                              "real_data": True})


def _concept_transition(concept: int, vocab: int) -> np.ndarray:
    """Row-stochastic transition matrix, deterministic per concept.

    Transitions are PEAKED (geometric weights over 8 successors), not
    uniform: with equal-weight successors the Bayes-optimal next-char
    accuracy is only 1/8 and argmax is an arbitrary tie-break, so "the
    model learns" is unobservable. Geometric weights put ~0.5 mass on the
    top successor — a trained model demonstrably beats the 1/90 chance
    floor (cf. real Shakespeare text, whose bigram distribution is
    similarly peaked)."""
    rng = np.random.default_rng(7919 + concept)
    logits = rng.normal(0, 1, size=(vocab, vocab))
    top = np.argsort(logits, axis=1)[:, -8:]
    mat = np.full((vocab, vocab), 1e-3)
    weights = 0.5 ** np.arange(8)[::-1]     # argsort ascending: last = top-1
    for i in range(vocab):
        mat[i, top[i]] += weights
    return mat / mat.sum(axis=1, keepdims=True)


def _num_concepts(change_points: np.ndarray) -> int:
    """Concepts a change-point matrix names, at least two."""
    return max(int(change_points.max()) + 1, 2)


def _affine_maps(n_concepts: int, vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """Per concept k the affine language ``next = (a_k * cur + b_k) mod V``:
    a deterministic successor map whose parameters (the language
    statistics) change at drift points. The same draw for every data set
    that uses it."""
    crng = np.random.default_rng(104729)
    a = crng.integers(2, vocab - 1, size=n_concepts)
    b = crng.integers(0, vocab, size=n_concepts)
    return a, b


def generate_word_drift(
    change_points: np.ndarray,
    train_iterations: int,
    num_clients: int,
    sample_num: int,
    noise_prob: float = 0.0,
    time_stretch: int = 1,
    seed: int = 0,
    seq_len: int = 20,
    vocab: int = 10000,
    data_dir: str = "./data",
) -> DriftDataset:
    """Word-level next-word-prediction drift (StackOverflow NWP scale,
    reference fedml_api/data_preprocessing/stackoverflow_nwp/, WordLSTM
    model rnn.py:36-67).

    Real TFF StackOverflow files under ``data_dir`` are preferred (see
    module docstring). Hermetic fallback: at 10k vocab a dense Markov
    matrix would be 800 MB per concept, so each concept k is instead an
    affine language: next = (a_k * cur + b_k) mod V with per-step uniform
    noise — a deterministic map the embedding LSTM can learn, whose
    parameters (the language statistics) change at drift points.
    """
    rng = np.random.default_rng(seed)
    T = train_iterations

    corpus = _try_load_word_corpus(data_dir, vocab, min_len=seq_len + 2)
    if corpus is not None:
        concepts = concept_matrix(change_points, T + 1, num_clients,
                                  time_stretch)
        return _real_text_windows(corpus, concepts, num_clients, sample_num,
                                  seq_len, vocab, rng, noise_prob,
                                  "stackoverflow_nwp")

    n_concepts = _num_concepts(change_points)
    a, b = _affine_maps(n_concepts, vocab)

    x = np.zeros((num_clients, T + 1, sample_num, seq_len), dtype=np.int32)
    y = np.zeros((num_clients, T + 1, sample_num), dtype=np.int32)
    concepts = concept_matrix(change_points, T + 1, num_clients, time_stretch)
    for t in range(T + 1):
        for c in range(num_clients):
            k = int(concepts[t, c]) % n_concepts
            seq = np.zeros((sample_num, seq_len + 1), dtype=np.int64)
            seq[:, 0] = rng.integers(0, vocab, size=sample_num)
            noise = rng.random((sample_num, seq_len)) < 0.1
            repl = rng.integers(0, vocab, size=(sample_num, seq_len))
            for s in range(seq_len):
                nxt = (a[k] * seq[:, s] + b[k]) % vocab
                seq[:, s + 1] = np.where(noise[:, s], repl[:, s], nxt)
            x[c, t] = seq[:, :seq_len].astype(np.int32)
            ys = seq[:, seq_len].astype(np.int32)
            if noise_prob > 0:
                flip = rng.random(sample_num) < noise_prob
                ys = np.where(flip, rng.integers(0, vocab, size=sample_num), ys)
            y[c, t] = ys
    return DriftDataset(x=x, y=y, num_classes=vocab, concepts=concepts,
                        name="stackoverflow_nwp", is_sequence=True,
                        meta={"vocab": vocab, "seq_len": seq_len})


def generate_text_drift(
    change_points: np.ndarray,
    train_iterations: int,
    num_clients: int,
    sample_num: int,
    noise_prob: float = 0.0,
    time_stretch: int = 1,
    seed: int = 0,
    seq_len: int = SEQ_LEN,
    vocab: int = VOCAB_SIZE,
    data_dir: str = "./data",
) -> DriftDataset:
    rng = np.random.default_rng(seed)
    T = train_iterations

    corpus = _try_load_char_corpus(data_dir, min_len=seq_len + 2)
    if corpus is not None:
        concepts = concept_matrix(change_points, T + 1, num_clients,
                                  time_stretch)
        return _real_text_windows(corpus, concepts, num_clients, sample_num,
                                  seq_len, vocab, rng, noise_prob,
                                  "shakespeare")

    chains = [_concept_transition(k, vocab)
              for k in range(_num_concepts(change_points))]

    x = np.zeros((num_clients, T + 1, sample_num, seq_len), dtype=np.int32)
    y = np.zeros((num_clients, T + 1, sample_num), dtype=np.int32)
    concepts = concept_matrix(change_points, T + 1, num_clients, time_stretch)
    for t in range(T + 1):
        for c in range(num_clients):
            concept = int(concepts[t, c])
            P = chains[concept % len(chains)]
            # Vectorised Markov rollout: [N, seq_len + 1]
            seq = np.zeros((sample_num, seq_len + 1), dtype=np.int32)
            seq[:, 0] = rng.integers(1, vocab, size=sample_num)
            u = rng.random((sample_num, seq_len))
            cdf = np.cumsum(P, axis=1)
            for s in range(seq_len):
                seq[:, s + 1] = (u[:, s, None] < cdf[seq[:, s]]).argmax(axis=1)
            x[c, t] = seq[:, :seq_len]
            ys = seq[:, seq_len]
            if noise_prob > 0:
                flip = rng.random(sample_num) < noise_prob
                ys = np.where(flip, rng.integers(0, vocab, size=sample_num), ys)
            y[c, t] = ys
    return DriftDataset(x=x, y=y, num_classes=vocab, concepts=concepts,
                        name="shakespeare", is_sequence=True,
                        meta={"vocab": vocab, "seq_len": seq_len})


# ----------------------------------------------------------------------
# next-token data with a label per token
# the unigram law: p(rank r) ~ 1 / r, Zipf's own exponent for words. (At 1.5
# one id is a fifth of all tokens, every position's attention reads much the
# same mean, and a randomly initialised router sends a quarter to a half of
# the tokens to one expert: CPU count at the published cut, PERF.md, PR 29.)
ZIPF_EXPONENT = 1.0
FOLLOW_PROB = 0.5        # share of tokens that follow the affine map


def _zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    return np.cumsum(p / p.sum())


def _rank_permutation(concept: int, vocab: int) -> np.ndarray:
    """Which id holds each rank of the unigram law under ``concept``."""
    return np.random.default_rng(7919 + concept).permutation(vocab)


def generate_token_drift(
    change_points: np.ndarray,
    train_iterations: int,
    num_clients: int,
    sample_num: int,
    time_stretch: int = 1,
    seed: int = 0,
    seq_len: int = SEQ_LEN,
    vocab: int = VOCAB_SIZE,
    zipf_exponent: float = ZIPF_EXPONENT,
    follow_prob: float = FOLLOW_PROB,
) -> DriftDataset:
    """Next-token data for a decoder: ``x`` [C, T+1, N, L] token ids and
    ``y`` [C, T+1, N, L] = ``x`` shifted by one, a label per token.

    Each concept k is a language over ``vocab`` ids (the rows of the
    vocabulary that the model holds): a Zipfian unigram law whose ranks the
    concept permutes (``_rank_permutation``), mixed with the affine successor
    map of ``_affine_maps``: with probability ``follow_prob`` the next token
    is ``(a_k * cur + b_k) mod V``, else a fresh draw from the concept's
    unigram law. A drift changes both. The unigram part is what a few local
    steps can learn (the frequent ids differ between concepts), the
    successor map what longer training can. Fixed-length sequences, every
    one from a fresh start: no packing and no document boundaries. Always
    made from the seed: there is no on-disk form.
    """
    rng = np.random.default_rng(seed)
    T1 = train_iterations + 1
    concepts = concept_matrix(change_points, T1, num_clients, time_stretch)
    n_concepts = _num_concepts(change_points)
    a, b = _affine_maps(n_concepts, vocab)
    perms = np.stack([_rank_permutation(k, vocab) for k in range(n_concepts)])
    cdf = _zipf_cdf(vocab, zipf_exponent)

    k = (concepts.T % n_concepts)[:, :, None, None]          # [C, T1, 1, 1]
    shape = (num_clients, T1, sample_num, seq_len + 1)
    ranks = np.minimum(np.searchsorted(cdf, rng.random(shape)), vocab - 1)
    fresh = perms[np.broadcast_to(k, shape), ranks].astype(np.int64)
    follow = rng.random(shape) < follow_prob
    a_k, b_k = a[k[..., 0]], b[k[..., 0]]                    # [C, T1, 1]
    seq = fresh.copy()
    for s in range(seq_len):
        nxt = (a_k * seq[..., s] + b_k) % vocab
        seq[..., s + 1] = np.where(follow[..., s + 1], nxt, fresh[..., s + 1])
    seq = seq.astype(np.int32)
    return DriftDataset(
        x=seq[..., :-1], y=seq[..., 1:], num_classes=vocab,
        concepts=concepts, name="token_drift", is_sequence=True,
        meta={"vocab": vocab, "seq_len": seq_len,
              "zipf_exponent": zipf_exponent, "follow_prob": follow_prob})


NOISE_LEVELS = 1000      # t = (level + 1) / NOISE_LEVELS: U[1e-3, 1] on a grid
DIFFUSION_BLOCK = 4      # tokens a block: the block of SDAR's chat models


def generate_token_diffusion(
    change_points: np.ndarray,
    train_iterations: int,
    num_clients: int,
    sample_num: int,
    time_stretch: int = 1,
    seed: int = 0,
    seq_len: int = SEQ_LEN,
    vocab: int = VOCAB_SIZE,
    zipf_exponent: float = ZIPF_EXPONENT,
    follow_prob: float = FOLLOW_PROB,
) -> DriftDataset:
    """Block-diffusion data for a decoder: ``x`` [C, T+1, N, 2L] token ids,
    a noisy copy x_t followed by the clean copy x_0, and ``y`` [C, T+1, N,
    L], a packed label a noisy position.

    x_0 is ``generate_token_drift``'s law (the same concepts and drift) over
    the first ``vocab - 1`` ids; id ``vocab - 1`` is the mask. Each block of
    ``DIFFUSION_BLOCK`` tokens of a sequence draws a noise level t uniformly
    from {1, ..., NOISE_LEVELS} / NOISE_LEVELS, and each of its tokens is
    replaced by the mask with probability t. The label of a masked position is
    ``clean id + vocab x (level)``, where t = (level + 1) / NOISE_LEVELS, and
    -1 at the others (``models/sdar_moe.py::unpack_labels``). The noise is
    drawn here once, from the seed, and not afresh at each step.
    """
    block = DIFFUSION_BLOCK
    if seq_len % block:
        raise ValueError(f"seq_len {seq_len} is not a whole number of "
                         f"blocks of {block}")
    clean = generate_token_drift(
        change_points, train_iterations, num_clients, sample_num,
        time_stretch, seed, seq_len=seq_len, vocab=vocab - 1,
        zipf_exponent=zipf_exponent, follow_prob=follow_prob)
    x0 = clean.x
    rng = np.random.default_rng([seed, 0xB10C])
    level = np.repeat(rng.integers(0, NOISE_LEVELS,
                                   size=x0.shape[:-1] + (seq_len // block,)),
                      block, axis=-1)
    masked = rng.random(x0.shape) < (level + 1) / NOISE_LEVELS
    x = np.concatenate([np.where(masked, vocab - 1, x0), x0], axis=-1)
    y = np.where(masked, x0 + vocab * level, -1)
    return DriftDataset(
        x=x.astype(np.int32), y=y.astype(np.int32), num_classes=vocab,
        concepts=clean.concepts, name="token_diffusion", is_sequence=True,
        meta={**clean.meta, "vocab": vocab, "diffusion_block": block,
              "mask_id": vocab - 1, "noise_levels": NOISE_LEVELS})
