"""Text NLP chain: tokenization, language detection, stop words, n-grams,
similarity.

Parity: reference ``core/.../stages/impl/feature/{TextTokenizer,
LangDetector, OpStopWordsRemover, OpNGram, NGramSimilarity,
TextLenTransformer}.scala`` and ``core/.../utils/text/*``. The reference
rides Lucene analyzers + the Optimaize detector; here tokenization is a
unicode word-regex analyzer with a CJK/Thai character-bigram path (the
LuceneTextAnalyzer/CJKAnalyzer analog) and language identification is the
character-n-gram profile detector in ``ops/lang.py`` (~30 languages, the
Optimaize/textcat family). All of these are host stages (string work stays
off the device; SURVEY §7 hard part #2).
"""

from __future__ import annotations

import re
from typing import Optional

import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.ops.lang import detect_language_ngram, language_scores
from transmogrifai_tpu.stages.base import HostTransformer
from transmogrifai_tpu.types import feature_types as ft

__all__ = [
    "TextTokenizer", "LangDetector", "OpStopWordsRemover", "OpNGram",
    "NGramSimilarity", "TextLenTransformer", "STOP_WORDS",
    "simple_tokenize", "detect_language",
    "RegexTokenizer", "TextToMultiPickList", "SetJaccardSimilarity",
]

_WORD_RE = re.compile(r"[^\W_]+", re.UNICODE)

#: scripts written without spaces: tokens segment into character bigrams
#: (the Lucene CJKAnalyzer convention)
_BIGRAM_RANGES = (
    (0x2E80, 0x9FFF),    # CJK radicals .. unified ideographs
    (0x3040, 0x30FF),    # hiragana + katakana (inside the range above)
    (0xF900, 0xFAFF),    # CJK compatibility
    (0x0E00, 0x0E7F),    # Thai
)

#: below this character no token needs the per-character script walk
_FIRST_BIGRAM_CHAR = chr(min(lo for lo, _ in _BIGRAM_RANGES))

#: per-language stopword profiles (removal; detection rides ops/lang.py)
STOP_WORDS: dict[str, frozenset] = {
    "en": frozenset("the a an and or of to in is are was were be been i you "
                    "he she it we they this that with for on at by from as "
                    "not no but if then so what which who whom".split()),
    "fr": frozenset("le la les un une des et ou de du au aux en est sont "
                    "était je tu il elle nous vous ils elles ce cette avec "
                    "pour sur par ne pas mais si que qui".split()),
    "de": frozenset("der die das ein eine und oder von zu in ist sind war "
                    "waren ich du er sie es wir ihr mit für auf bei aus "
                    "nicht kein aber wenn dann was welche wer".split()),
    "es": frozenset("el la los las un una unos unas y o de del al en es son "
                    "era yo tú él ella nosotros vosotros ellos con para "
                    "sobre por no pero si que quien".split()),
    "it": frozenset("il lo la i gli le un uno una e o di del della al in è "
                    "sono era io tu lui lei noi voi loro con per su da non "
                    "ma se che chi".split()),
    "pt": frozenset("o a os as um uma uns umas e ou de do da ao em é são "
                    "era eu tu ele ela nós vós eles com para sobre por não "
                    "mas se que quem".split()),
    "nl": frozenset("de het een en of van naar in is zijn was waren ik jij "
                    "hij zij wij jullie met voor op bij uit niet geen maar "
                    "als dan wat welke wie".split()),
    "sv": frozenset("och det att i en jag hon som han på den med var sig "
                    "för så till är men ett om hade de av icke mig du "
                    "henne då sin nu har inte hans honom".split()),
    "da": frozenset("og i jeg det at en den til er som på de med han af "
                    "for ikke der var mig sig men et har om vi min havde "
                    "ham hun nu over da fra du ud".split()),
    "no": frozenset("og i jeg det at en et den til er som på de med han "
                    "av ikke der så var meg seg men ett har om vi min "
                    "mitt ha hadde hun nå over da ved fra du ut".split()),
    "fi": frozenset("olla olen on ja se ei että en oli hän minä joka mitä "
                    "tämä mutta niin kuin sen sitä tai kun nyt jos mikä "
                    "ole vain minun hänen ovat sinä me he".split()),
    "pl": frozenset("i w nie na się że z do to jak o co tak jest po a ale "
                    "czy za przez od dla przy bez być może ten ta te go "
                    "ich jego jej mnie ciebie".split()),
    "cs": frozenset("a v na se že je s z do o k i ale jako za by pro tak "
                    "po co když už jen při od být ten tato toto jsem jsi "
                    "jsou byl byla bylo nebo ani".split()),
    "ro": frozenset("și în a la cu de pe că nu este sunt un o care mai "
                    "din pentru dar dacă ce așa după cum fără sau fi am "
                    "ai are acest această eu tu el ea noi".split()),
    "hu": frozenset("a az és hogy nem is ez egy van volt de meg csak már "
                    "el mint még ki mi ha vagy lesz lehet más aki amely "
                    "én te ő mert azt ezt nagyon".split()),
    "tr": frozenset("ve bir bu da de için ile ne gibi daha çok ama o ben "
                    "sen biz siz onlar mi mu değil var yok olan olarak "
                    "kadar sonra önce her şey ki en".split()),
    "ru": frozenset("и в не на я что он с как это а то все она так его но "
                    "они к у же вы за бы по ее мне было вот от меня о из "
                    "ему теперь когда даже ну ли если уже или".split()),
    "id": frozenset("yang dan di ini itu dengan untuk tidak dari dalam "
                    "akan pada juga saya kamu dia kami mereka ada bisa "
                    "sudah atau ke oleh karena jika seperti".split()),
}


def _needs_bigrams(ch: str) -> bool:
    cp = ord(ch)
    return any(lo <= cp <= hi for lo, hi in _BIGRAM_RANGES)


def simple_tokenize(text: str, lowercase: bool = True,
                    min_token_length: int = 1) -> list[str]:
    """Unicode word tokens; runs in space-less scripts (CJK, kana, Thai)
    segment into overlapping character bigrams. Mixed-script tokens split
    at script boundaries first (the CJKAnalyzer convention), so 'abc漢字'
    yields 'abc' + the CJK bigrams regardless of which script leads."""
    if lowercase:
        text = text.lower()
    out = []
    for tok in _WORD_RE.findall(text):
        if max(tok) < _FIRST_BIGRAM_CHAR:   # no space-less script in it
            if len(tok) >= min_token_length:
                out.append(tok)
            continue
        start = 0
        while start < len(tok):
            is_cjk = _needs_bigrams(tok[start])
            end = start + 1
            while end < len(tok) and _needs_bigrams(tok[end]) == is_cjk:
                end += 1
            run = tok[start:end]
            start = end
            if is_cjk:
                if len(run) == 1:
                    out.append(run)
                else:
                    out.extend(run[i:i + 2] for i in range(len(run) - 1))
            elif len(run) >= min_token_length:
                out.append(run)
    return out


def detect_language(text: str) -> Optional[str]:
    """Character-n-gram profile detection over ~30 languages (ops/lang.py);
    None when the text carries no alphabetic signal."""
    return detect_language_ngram(text)


_TAG_RE = re.compile(
    r"<!--.*?-->|<script\b.*?</script\s*>|<style\b.*?</style\s*>|<[^>]*>",
    re.IGNORECASE | re.DOTALL)


def strip_html(text: str) -> str:
    """Lucene HTMLStripCharFilter analog: drop tags/comments/script/style
    bodies, decode entities (stdlib ``html.unescape``: full named/decimal/
    hex table, single-pass so ``&amp;lt;`` stays ``&lt;``, graceful on
    out-of-range numeric references), keep the visible text."""
    import html as _html
    out = _html.unescape(_TAG_RE.sub(" ", text))
    return out.replace("\xa0", " ")  # &nbsp; decodes to NBSP; normalize


class TextTokenizer(HostTransformer):
    """Text -> TextList through the analyzer chain (reference
    ``TextTokenizer.scala:293`` via Lucene): optional HTML stripping,
    tokenization, language-aware stopword filter, Porter stemming for
    English (the EnglishAnalyzer's PorterStemFilter stage)."""

    in_types = (ft.Text,)
    out_type = ft.TextList

    def __init__(self, lowercase: bool = True, min_token_length: int = 1,
                 auto_detect_language: bool = False,
                 filter_stopwords: bool = False,
                 default_language: str = "en",
                 strip_html_tags: bool = False,
                 stem: bool = False,
                 uid: Optional[str] = None):
        self.lowercase = lowercase
        self.min_token_length = min_token_length
        self.auto_detect_language = auto_detect_language
        self.filter_stopwords = filter_stopwords
        self.default_language = default_language
        self.strip_html_tags = strip_html_tags
        self.stem = stem
        super().__init__(uid=uid)

    def transform_row(self, value):
        if value is None:
            return []
        if self.strip_html_tags:
            value = strip_html(value)
        toks = simple_tokenize(value, self.lowercase, self.min_token_length)
        lang = None
        if self.filter_stopwords or self.stem:
            lang = (detect_language(value) if self.auto_detect_language
                    else self.default_language) or self.default_language
        if self.filter_stopwords:
            stop = STOP_WORDS.get(lang, frozenset())
            toks = [t for t in toks if t not in stop]
        if self.stem and lang == "en":
            from transmogrifai_tpu.ops.stemmer import porter_stem
            toks = [porter_stem(t) for t in toks]
        return toks


class LangDetector(HostTransformer):
    """Text -> RealMap of language -> confidence for the top candidates
    (reference LangDetector emits the Optimaize detected-language score
    map)."""

    in_types = (ft.Text,)
    out_type = ft.RealMap

    def __init__(self, top_k: int = 3, uid: Optional[str] = None):
        self.top_k = top_k
        super().__init__(uid=uid)

    def transform_row(self, value):
        if value is None:
            return {}
        scores = language_scores(value)
        if not scores:
            return {}
        top = sorted(scores.items(), key=lambda kv: -kv[1])[:self.top_k]
        return {k: float(v) for k, v in top if v > 0}


class OpStopWordsRemover(HostTransformer):
    in_types = (ft.TextList,)
    out_type = ft.TextList

    def __init__(self, language: str = "en",
                 extra_stop_words: tuple = (),
                 uid: Optional[str] = None):
        self.language = language
        self.extra_stop_words = tuple(extra_stop_words)
        super().__init__(uid=uid)

    def transform_row(self, tokens):
        stop = STOP_WORDS.get(self.language, frozenset()) | set(
            self.extra_stop_words)
        return [t for t in (tokens or []) if t.lower() not in stop]


class OpNGram(HostTransformer):
    in_types = (ft.TextList,)
    out_type = ft.TextList

    def __init__(self, n: int = 2, uid: Optional[str] = None):
        if n < 1:
            raise ValueError("n must be >= 1")
        self.n = n
        super().__init__(uid=uid)

    def transform_row(self, tokens):
        toks = tokens or []
        n = self.n
        return [" ".join(toks[i:i + n]) for i in range(len(toks) - n + 1)]


def _char_ngrams(s: str, n: int) -> set:
    s = s.lower()
    return {s[i:i + n] for i in range(max(len(s) - n + 1, 1))}


class NGramSimilarity(HostTransformer):
    """(Text, Text) -> RealNN Jaccard similarity of character n-grams
    (reference NGramSimilarity/JaccardSimilarity)."""

    in_types = (ft.Text, ft.Text)
    out_type = ft.RealNN

    def __init__(self, n: int = 3, uid: Optional[str] = None):
        self.n = n
        super().__init__(uid=uid)

    def transform_row(self, a, b):
        if not a or not b:
            return 0.0
        ga, gb = _char_ngrams(a, self.n), _char_ngrams(b, self.n)
        union = len(ga | gb)
        return len(ga & gb) / union if union else 0.0


class TextLenTransformer(HostTransformer):
    """Text/TextList -> total text length vector (reference
    TextLenTransformer)."""

    variadic = True
    in_types = (ft.FeatureType,)
    out_type = ft.OPVector

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid=uid)

    def transform_row(self, *values):
        out = []
        for v in values:
            if v is None:
                out.append(0.0)
            elif isinstance(v, str):
                out.append(float(len(v)))
            elif isinstance(v, (list, tuple, set)):
                out.append(float(sum(len(str(x)) for x in v)))
            else:
                out.append(0.0)
        return np.asarray(out, dtype=np.float32)


class RegexTokenizer(HostTransformer):
    """Text -> TextList of regex tokens (reference RichTextFeature
    ``tokenizeRegex`` via LuceneRegexTextAnalyzer -> Lucene PatternTokenizer,
    ``RichTextFeature.scala:378``, ``LuceneTextAnalyzer.scala:139``).

    ``group`` = -1 SPLITS on the pattern (Lucene's "equivalent to split",
    dropping empty tokens — ``tokenizeRegex(pattern="\\s+")`` yields words);
    ``group`` >= 0 takes that capture group of each match (0 = whole match).
    Tokens shorter than ``min_token_length`` drop.
    """

    in_types = (ft.Text,)
    out_type = ft.TextList

    def __init__(self, pattern: str = r"\W+", group: int = -1,
                 min_token_length: int = 1, lowercase: bool = True,
                 uid: Optional[str] = None):
        self.pattern = pattern
        self.group = int(group)
        self.min_token_length = int(min_token_length)
        self.lowercase = bool(lowercase)
        self._re = re.compile(pattern, re.UNICODE)
        super().__init__(uid=uid)

    def transform_row(self, value):
        if value is None:
            return []
        if self.lowercase:
            value = value.lower()
        if self.group < 0:
            toks = [t for t in self._re.split(value) if t]
        else:
            toks = [m.group(self.group) or ""
                    for m in self._re.finditer(value)]
        return [t for t in toks if len(t) >= self.min_token_length]


class TextToMultiPickList(HostTransformer):
    """Text -> single-element MultiPickList (reference RichTextFeature
    ``toMultiPickList``); empty set when missing."""

    in_types = (ft.Text,)
    out_type = ft.MultiPickList

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid=uid)

    def transform_row(self, value):
        return set() if value is None else {value}


class SetJaccardSimilarity(HostTransformer):
    """(MultiPickList, MultiPickList) -> RealNN Jaccard similarity of the
    two sets (reference ``JaccardSimilarity.scala`` / RichSetFeature
    ``jaccardSimilarity``): |a & b| / |a | b|, and 1.0 when BOTH sides are
    empty (the reference's documented convention)."""

    in_types = (ft.MultiPickList, ft.MultiPickList)
    out_type = ft.RealNN

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid=uid)

    def transform_row(self, a, b):
        sa = set(a or ())
        sb = set(b or ())
        if not sa and not sb:
            return 1.0
        union = len(sa | sb)
        return len(sa & sb) / union
