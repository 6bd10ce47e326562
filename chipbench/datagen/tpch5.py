"""TPC-H customer / orders / lineitem / supplier / nation for Q21 and
Q13: ``datagen/tpch3.py``'s key structure (its chunks give every
order's key, customer and date and every line's order and ship date)
with the columns the two queries read added from streams of their own,
by dbgen's rules (TPC-H v3 clause 4.2.3).  Dates are INT day numbers.

``customer``: ``c_custkey`` 1..150,000 x scale.

``orders``: tpch3's ``o_orderkey`` (sparse) and ``o_custkey`` (no
order for a customer whose key divides by 3), ``o_orderstatus`` F when
every line of the order shipped on or before 1995-06-17 (dbgen's
CURRENTDATE), O when every line shipped after it, P otherwise, and
``o_comment``: a cut of 19..78 bytes at a random offset of a text pool
written by the spec's grammar and word lists (clause 4.2.2.10 and
4.2.2.13: sentences of noun, verb and prepositional phrases over the
nouns, verbs, adjectives, adverbs, prepositions, auxiliaries and
terminators), built once from a fixed seed as dbgen builds its pool.

``lineitem``: tpch3's ``l_orderkey`` and ship date; ``l_partkey``
uniform in 1..200,000 x scale (not written), ``l_suppkey`` one of the
part's four suppliers, ``(p + i x (S/4 + (p-1)/S)) mod S + 1`` with
``i`` drawn in 0..3 and S suppliers, ``l_commitdate`` = order date +
30..90 days, ``l_receiptdate`` = ship date + 1..30 days.

``supplier``: ``s_suppkey`` 1..10,000 x scale, ``s_name``
``Supplier#%09d``, ``s_nationkey`` uniform in 0..24.  ``nation``: the
spec's 25 rows (SAUDI ARABIA = 20).

Not dbgen's: tpch3's (exactly 4 lines an order on average, a balanced
multiset shuffled), a text pool of ``TEXT_POOL_BYTES`` where dbgen
writes 300 MB, and the grammar's weights (``_WORDS``) as this module
states them.  A table's rows are the same whichever other tables are
asked for: every stream runs for every chunk."""
import os
import zlib

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as papq

from . import table_rng, tpch3

ROWS_PER_SF = {"customer": 150_000, "orders": 1_500_000,
               "lineitem": 6_000_000, "supplier": 10_000, "nation": 0}
TABLES = tuple(ROWS_PER_SF)
CURRENT_DAY = 9298                      # dbgen's CURRENTDATE, 1995-06-17
COMMENT_BYTES = (19, 78)                # o_comment: text string [19, 78]
TEXT_POOL_BYTES = 1 << 22
NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
           "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
           "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
           "UNITED KINGDOM", "UNITED STATES"]

#: the grammar's word lists, "word|weight" (weight 1 where none is
#: given; "_" stands for a space)
_WORDS = {
    "N": "packages|40 requests|40 accounts|40 deposits|40 foxes|20 ideas|20 "
         "theodolites|20 pinto_beans|20 instructions|20 dependencies|10 "
         "excuses|10 platelets|10 asymptotes|10 courts|5 dolphins|5 "
         "multipliers sauternes warthogs frets dinos attainments somas "
         "Tiresias patterns forges braids hockey_players frays warhorses "
         "dugouts notornis epitaphs pearls tithes waters orbits gifts "
         "sheaves depths sentiments decoys realms pains grouches escapades",
    "V": "sleep|20 wake|20 are|20 cajole|20 haggle|20 nag|10 use|10 "
         "boost|10 affix|5 detect|5 integrate|5 maintain nod was lose "
         "sublate solve thrash promise engage hinder print x-ray breach eat "
         "grow impress mold poach serve run dazzle snooze doze unwind "
         "kindle play hang believe doubt",
    "J": "furious sly careful blithe quick fluffy slow quiet ruthless thin "
         "close dogged daring brave stealthy permanent enticing idle busy "
         "regular|50 final|40 ironic|40 even|30 bold|20 silent|10 "
         "express|20 pending|20 special|20 unusual|10",
    "D": "sometimes always never furiously|50 slyly|50 carefully|50 "
         "blithely|40 quickly|30 fluffily|20 slowly quietly ruthlessly "
         "thinly closely doggedly daringly bravely stealthily permanently "
         "enticingly idly busily regularly finally ironically evenly "
         "boldly silently",
    "P": "about|50 above|50 according_to|50 across|50 after|50 against|40 "
         "along|40 alongside_of|30 among|30 around|20 at|10 atop before "
         "behind beneath beside besides between beyond by despite during "
         "except for from in_place_of inside instead_of into near of on "
         "outside over past since through throughout to toward under "
         "until up upon without with within",
    "X": "do may might shall will would can could should ought_to must "
         "will_have_to shall_have_to could_have_to should_have_to "
         "must_have_to need_to try_to",
    "T": ".|50 ;|1 :|1 ?|1 !|1 --|1",
}
_SENTENCES = {"N V T": 3, "N V P T": 3, "N V N T": 3, "N P V N T": 1,
              "N P V P T": 1}
_NOUN_PHRASES = {"N": 10, "J N": 20, "J , J N": 10, "D J N": 50}
_VERB_PHRASES = {"V": 30, "X V": 1, "V D": 40, "X V D": 1}


def row_counts(scale: float) -> dict:
    """Rows of each table at ``scale``, the same for every seed."""
    n = tpch3.row_counts(scale)
    n["supplier"] = max(int(ROWS_PER_SF["supplier"] * scale), 25)
    n["nation"] = len(NATIONS)
    return n


def _choices(spec: dict, rng, size: int) -> list:
    keys = list(spec)
    w = np.array([spec[k] for k in keys], np.float64)
    return [keys[i] for i in rng.choice(len(keys), size, p=w / w.sum())]


def _word_list(text: str):
    words, weights = [], []
    for item in text.split():
        word, _, weight = item.partition("|")
        words.append(word.replace("_", " "))
        weights.append(int(weight or 1))
    w = np.array(weights, np.float64)
    return words, w / w.sum()


def text_pool(n_bytes: int = TEXT_POOL_BYTES) -> bytes:
    """The spec's text pool: sentences of the grammar, space-separated,
    cut at ``n_bytes``; the same bytes for every seed."""
    rng = np.random.default_rng(zlib.crc32(b"tpch5.text_pool"))
    lists = {k: _word_list(v) for k, v in _WORDS.items()}
    n_sent = n_bytes // 30 + 1000
    draws = {k: iter(rng.choice(len(ws), 8 * n_sent, p=p).tolist())
             for k, (ws, p) in lists.items()}
    sentences = _choices(_SENTENCES, rng, n_sent)
    nouns = iter(_choices(_NOUN_PHRASES, rng, 3 * n_sent))
    verbs = iter(_choices(_VERB_PHRASES, rng, n_sent))

    def word(kind):
        return lists[kind][0][next(draws[kind])]

    def phrase(shape):
        out = []
        for part in shape.split():
            out.append(part if part == "," else word(part))
        return " ".join(out).replace(" ,", ",")

    out, size = [], 0
    for shape in sentences:
        parts = []
        for part in shape.split():
            if part == "N":
                parts.append(phrase(next(nouns)))
            elif part == "V":
                parts.append(phrase(next(verbs)))
            elif part == "P":
                parts.append(f"{word('P')} the {word('N')}")
            else:
                parts[-1] += word("T")
        s = " ".join(parts)
        out.append(s)
        size += len(s) + 1
        if size >= n_bytes:
            break
    return " ".join(out).encode()[:n_bytes]


def comments(pool: np.ndarray, rng, k: int) -> pa.Array:
    """``k`` cuts of ``pool`` (uint8) of ``COMMENT_BYTES`` lengths at
    random offsets, as one Arrow string array built from its buffers."""
    lens = rng.integers(COMMENT_BYTES[0], COMMENT_BYTES[1] + 1, k)
    starts = rng.integers(0, pool.shape[0] - lens + 1)
    offsets = np.zeros(k + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    shift = np.repeat(starts - offsets[:-1], lens)
    data = pool[np.arange(offsets[-1], dtype=np.int64) + shift]
    return pa.StringArray.from_buffers(
        k, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data))


def chunks(n: dict, seed: int, pool: np.ndarray):
    """-> (orders columns, lineitem columns) for every chunk of tpch3's
    orders in key order, with this module's columns added."""
    o_rng = table_rng(seed, "orders.q13q21")
    l_rng = table_rng(seed, "lineitem.q13q21")
    n_supp = n["supplier"]
    n_parts = max(int(200_000 * n["lineitem"] / ROWS_PER_SF["lineitem"]), 1)
    for orders, lines in tpch3.chunks(n, seed):
        okey, k = orders["o_orderkey"], len(orders["o_orderkey"])
        of_line = np.searchsorted(okey, lines["l_orderkey"])
        m = len(of_line)
        part = l_rng.integers(1, n_parts + 1, m)
        i = l_rng.integers(0, 4, m)
        supp = (part + i * (n_supp // 4 + (part - 1) // n_supp)) % n_supp + 1
        ship = lines["l_shipdate"].astype(np.int64)
        commit = orders["o_orderdate"][of_line] + l_rng.integers(30, 91, m)
        receipt = ship + l_rng.integers(1, 31, m)
        first = np.flatnonzero(np.r_[True, of_line[1:] != of_line[:-1]])
        late = (ship > CURRENT_DAY).astype(np.int8)
        all_o = np.minimum.reduceat(late, first).astype(bool)
        any_o = np.maximum.reduceat(late, first).astype(bool)
        status = np.where(all_o, 2, np.where(any_o, 1, 0)).astype(np.int8)
        yield ({"o_orderkey": okey,
                "o_custkey": orders["o_custkey"],
                "o_orderstatus": pa.DictionaryArray.from_arrays(
                    pa.array(status), pa.array(["F", "P", "O"])
                ).cast(pa.string()),
                "o_comment": comments(pool, o_rng, k)},
               {"l_orderkey": lines["l_orderkey"],
                "l_suppkey": supp.astype(np.int64),
                "l_commitdate": commit.astype(np.int32),
                "l_receiptdate": receipt.astype(np.int32)})


def _write_small(data_dir, n, seed, tables):
    if "customer" in tables:
        papq.write_table(pa.table({"c_custkey": np.arange(
            1, n["customer"] + 1, dtype=np.int64)}),
            os.path.join(data_dir, "customer.parquet"))
    if "supplier" in tables:
        key = np.arange(1, n["supplier"] + 1, dtype=np.int64)
        name = pc.binary_join_element_wise(
            "Supplier#", pc.utf8_lpad(
                pa.array(key).cast(pa.string()), 9, "0"), "")
        nation = table_rng(seed, "supplier").integers(0, 25, len(key))
        papq.write_table(pa.table({"s_suppkey": key, "s_name": name,
                                   "s_nationkey": nation.astype(np.int64)}),
                         os.path.join(data_dir, "supplier.parquet"))
    if "nation" in tables:
        papq.write_table(pa.table({
            "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
            "n_name": pa.array(NATIONS)}),
            os.path.join(data_dir, "nation.parquet"))


def refuse_engine_without_device_like() -> None:
    """End the run, cleanly and before any data is written, on an engine
    that cannot give this configuration a result: one whose planner
    refuses LIKE on the device (its rule checked LIKE's BOOLEAN output
    against the string signature) ends Q13 in a CPU operator, which the
    configuration's ``sql.test.enabled`` turns into an error, but only
    after Q21's first pass has compiled for minutes; and its LIKE
    kernels ask for a 34 GB allocation at one 2^20-row batch of
    comments.  A parent commit that cannot run a new configuration is
    to fail with an exit code other than 0, soon.  A generator used
    without the engine generates."""
    import importlib.util
    if importlib.util.find_spec("spark_rapids_tpu") is None:
        return
    from spark_rapids_tpu.kernels import strings
    if not hasattr(strings, "str_like_match"):
        raise SystemExit(
            "chipbench/datagen/tpch5.py: this engine plans LIKE on the "
            "CPU (Q13's ON clause); a run of schema tpch5 under "
            "sql.test.enabled cannot answer it")


def generate(data_dir: str, scale: float, seed: int, tables) -> dict:
    refuse_engine_without_device_like()
    n = row_counts(scale)
    tables = list(tables)
    for name in tables:
        if name not in TABLES:
            raise KeyError(f"tpch5 datagen has no table {name!r}")
    _write_small(data_dir, n, seed, tables)
    big = [t for t in ("orders", "lineitem") if t in tables]
    if big:
        pool = np.frombuffer(text_pool(), np.uint8)
        writers = {}
        try:
            for orders, lines in chunks(n, seed, pool):
                for name, columns in (("orders", orders),
                                      ("lineitem", lines)):
                    if name not in big:
                        continue
                    chunk = pa.table(columns)
                    if name not in writers:
                        writers[name] = papq.ParquetWriter(
                            os.path.join(data_dir, f"{name}.parquet"),
                            chunk.schema)
                    writers[name].write_table(chunk)
        finally:
            for w in writers.values():
                w.close()
    return {t: n[t] for t in tables}
