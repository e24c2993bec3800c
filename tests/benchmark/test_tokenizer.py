"""The benchmark's tokenizer: round trip, exact prompt counts, and a
session's history re-encoding to the ids the prefix cache holds."""

import random

import pytest

from benchmark.generators import common
from benchmark.tokenizer import WordTokenizer, count_words

TOK = WordTokenizer(32000)


@pytest.mark.parametrize("ids", [
    [], [0], [1, 2, 3], [31999], list(range(0, 32000, 997)),
    [random.Random(5).randrange(32000) for _ in range(600)],
])
def test_round_trip(ids):
    assert TOK.encode(TOK.decode(ids)) == ids
    assert count_words(TOK.decode(ids)) == len(ids)


def test_every_id_decodes_to_visible_text():
    # ByteTokenizer decodes ids >= 256 to nothing; this one never does
    assert all(TOK.decode([i]).strip() for i in (0, 255, 256, 257, 31999))


def test_out_of_range_ids_are_dropped_not_raised():
    assert TOK.decode([-1, 5, 32000]) == "t5 "


@pytest.mark.parametrize("n", [3, 32, 257, 1536])
def test_a_generated_prompt_has_exactly_the_tokens_asked_for(n):
    messages = common.single_message(random.Random(n), n, 32000)
    ids = TOK.encode(TOK.apply_chat_template(messages))
    assert len(ids) == n


def test_history_re_encodes_to_the_prefix_the_cache_holds():
    rng = random.Random(1)
    history = [{"role": "system", "content": common.random_words(rng, 50, 32000)},
               {"role": "user", "content": common.random_words(rng, 20, 32000)}]
    prompt1 = TOK.encode(TOK.apply_chat_template(history))
    generated = [rng.randrange(32000) for _ in range(30)]
    answer_text = TOK.decode(generated)  # what the client receives
    history2 = history + [{"role": "assistant", "content": answer_text},
                          {"role": "user", "content": common.random_words(rng, 9, 32000)}]
    prompt2 = TOK.encode(TOK.apply_chat_template(history2))
    assert prompt2[:len(prompt1) + len(generated)] == prompt1 + generated


def test_foreign_words_get_a_stable_id():
    assert TOK.encode("hello") == TOK.encode("hello")
    assert 0 <= TOK.encode("hello")[0] < 32000
