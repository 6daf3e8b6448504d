// ndsgen — seeded, chunk-parallel decision-support data generator.
//
// TPU-native replacement for the reference's native generation engine
// (tpcds-gen/dsdgen wrapper, see /root/reference/nds/tpcds-gen/ and
// nds_gen_data.py).  Unlike dsdgen this is a from-scratch generator: it
// produces a TPC-DS-*shaped* dataset (same 25 tables, same columns, same
// referential structure, same pipe-delimited .dat output contract and
// `{table}_{child}_{parallel}.dat` chunk naming) from a counter-based RNG,
// so that any chunking of the work produces byte-identical global content:
// the value stream of row r of table t depends only on (seed, t, r).
//
// CLI (dsdgen-compatible surface, cf. nds_gen_data.py:211-225):
//   ndsgen -scale <SF> -dir <outdir> [-parallel <N> -child <i>]
//          [-table <name>] [-update <k>] [-seed <s>]
//
//   -parallel/-child: generate only chunk i of N (1-based), all tables.
//   -update k: generate the k-th refresh set (s_* staging tables + the
//              delete/inventory_delete date-range tables).
//
// Money columns are written with 2 decimal places; NULL is an empty field;
// lines end with a trailing '|' exactly like dsdgen output.

#include <cinttypes>
#include <cmath>
#include <cstdarg>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

// ---------------------------------------------------------------------------
// Counter-based RNG
// ---------------------------------------------------------------------------

static inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ULL;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

struct Rng {
  uint64_t key;
  uint64_t ctr = 0;
  explicit Rng(uint64_t seed, uint64_t table_id, uint64_t row) {
    key = splitmix64(seed ^ (table_id * 0xA24BAED4963EE407ULL) ^
                     (row * 0x9FB21C651E98DF25ULL));
  }
  uint64_t next() { return splitmix64(key + (ctr++) * 0x632BE59BD9B4E019ULL); }
  // uniform in [lo, hi] inclusive
  int64_t range(int64_t lo, int64_t hi) {
    return lo + (int64_t)(next() % (uint64_t)(hi - lo + 1));
  }
  bool chance(double p) { return (next() >> 11) * 0x1.0p-53 < p; }
  // money in cents, uniform [lo_cents, hi_cents]
  int64_t cents(int64_t lo, int64_t hi) { return range(lo, hi); }
  // Zipf(s~1)-skewed pick in [1, n]: rank = floor(n^u) gives
  // P(rank <= k) = ln(k+1)/ln(n+1) — a handful of hot keys carry most
  // of the mass, like dsdgen's weighted distribution tables give real
  // NDS data (reference nds/tpcds-gen; uniform draws made every
  // selectivity and every join fan-out unrealistically flat).  The
  // rank is scattered over the key space by a coprime multiplier so
  // hot keys are spread out, not clustered at 1..k.  One next() call —
  // counter-stream stability for the re-derivation in gen_return.
  int64_t zipf(int64_t n) {
    if (n <= 1) return 1;
    double u = (next() >> 11) * 0x1.0p-53;  // [0, 1)
    double rf = exp(u * log((double)n + 1.0));
    int64_t rank = (int64_t)rf;  // 1..n
    if (rank < 1) rank = 1;
    if (rank > n) rank = n;
    static const uint64_t kScatter[] = {2654435761ULL, 1073741827ULL,
                                        805306457ULL, 100000007ULL};
    for (uint64_t p : kScatter) {
      uint64_t a = p % (uint64_t)n, b = (uint64_t)n;  // gcd(p, n) == 1?
      while (a) { uint64_t t = b % a; b = a; a = t; }
      if (b == 1) return (int64_t)(((uint64_t)(rank - 1) * p) % (uint64_t)n) + 1;
    }
    return rank;  // no coprime scatter (tiny n): unscattered rank
  }
};

// ---------------------------------------------------------------------------
// Calendar helpers (days <-> civil date; Julian day numbering like TPC-DS
// date_sk).  JD 2440588 == 1970-01-01.
// ---------------------------------------------------------------------------

static const int64_t JD_EPOCH_1970 = 2440588;
static const int64_t DATE_DIM_FIRST_JD = 2415022;  // 1900-01-02
static const int64_t DATE_DIM_ROWS = 73049;        // through 2100-01-01
static const int64_t SALES_FIRST_JD = 2450816;     // 1998-01-02
static const int64_t SALES_LAST_JD = 2452642;      // 2003-01-02

struct Civil {
  int y, m, d;
};

static Civil civil_from_days(int64_t z) {  // days since 1970-01-01
  z += 719468;
  int64_t era = (z >= 0 ? z : z - 146096) / 146097;
  unsigned doe = (unsigned)(z - era * 146097);
  unsigned yoe = (doe - doe / 1460 + doe / 36524 - doe / 146096) / 365;
  int64_t y = (int64_t)yoe + era * 400;
  unsigned doy = doe - (365 * yoe + yoe / 4 - yoe / 100);
  unsigned mp = (5 * doy + 2) / 153;
  unsigned d = doy - (153 * mp + 2) / 5 + 1;
  unsigned m = mp < 10 ? mp + 3 : mp - 9;
  return Civil{(int)(y + (m <= 2)), (int)m, (int)d};
}

static int64_t days_from_civil(int y, unsigned m, unsigned d) {
  y -= m <= 2;
  int64_t era = (y >= 0 ? y : y - 399) / 400;
  unsigned yoe = (unsigned)(y - era * 400);
  unsigned doy = (153 * (m > 2 ? m - 3 : m + 9) + 2) / 5 + d - 1;
  unsigned doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return era * 146097 + (int64_t)doe - 719468;
}

static int weekday(int64_t days) {  // 0=Sunday (TPC d_dow: 0=Sunday)
  return (int)(((days + 4) % 7 + 7) % 7);
}

// ---------------------------------------------------------------------------
// Output writer
// ---------------------------------------------------------------------------

struct Writer {
  FILE* f;
  char buf[1 << 16];
  explicit Writer(const std::string& path) {
    f = fopen(path.c_str(), "w");
    if (!f) {
      fprintf(stderr, "ndsgen: cannot open %s\n", path.c_str());
      exit(2);
    }
    setvbuf(f, buf, _IOFBF, sizeof(buf));
  }
  ~Writer() {
    if (ferror(f) || fclose(f) != 0) {
      fprintf(stderr, "ndsgen: write error (disk full?)\n");
      exit(3);
    }
  }
  void fint(int64_t v) { fprintf(f, "%" PRId64 "|", v); }
  void fnull() { fputc('|', f); }
  void fstr(const char* s) { fprintf(f, "%s|", s); }
  void fstr(const std::string& s) { fprintf(f, "%s|", s.c_str()); }
  void fmoney(int64_t c) {  // cents -> d.cc
    if (c < 0)
      fprintf(f, "-%" PRId64 ".%02d|", (-c) / 100, (int)((-c) % 100));
    else
      fprintf(f, "%" PRId64 ".%02d|", c / 100, (int)(c % 100));
  }
  void fdate(int64_t jd) {
    Civil c = civil_from_days(jd - JD_EPOCH_1970);
    fprintf(f, "%04d-%02d-%02d|", c.y, c.m, c.d);
  }
  void endrow() { fputc('\n', f); }
};

// ---------------------------------------------------------------------------
// Word pools
// ---------------------------------------------------------------------------

static const char* kStreetNames[] = {"Main", "Oak", "Park", "First", "Elm",
    "Second", "Washington", "Maple", "Cedar", "Pine", "Lake", "Hill", "Walnut",
    "Spring", "North", "Ridge", "Church", "Willow", "Mill", "Sunset", "Railroad",
    "Jackson", "River", "Highland", "Johnson", "View", "Forest", "Green",
    "Meadow", "Broad", "Chestnut", "Franklin", "College", "Smith", "Center",
    "Davis", "Wilson", "Birch", "Locust", "Dogwood"};
static const char* kStreetTypes[] = {"Street", "Avenue", "Boulevard", "Drive",
    "Lane", "Road", "Court", "Circle", "Way", "Parkway", "Pkwy", "Blvd", "Ave",
    "Dr", "Ln", "RD", "Ct", "Cir", "ST", "Wy"};
static const char* kCountries[] = {"United States"};
static const char* kLocationTypes[] = {"apartment", "condo", "single family"};
static const char* kFirstNames[] = {"James", "Mary", "John", "Patricia",
    "Robert", "Jennifer", "Michael", "Linda", "William", "Elizabeth", "David",
    "Barbara", "Richard", "Susan", "Joseph", "Jessica", "Thomas", "Sarah",
    "Charles", "Karen", "Christopher", "Nancy", "Daniel", "Lisa", "Matthew",
    "Margaret", "Anthony", "Betty", "Donald", "Sandra", "Mark", "Ashley",
    "Paul", "Dorothy", "Steven", "Kimberly", "Andrew", "Emily", "Kenneth",
    "Donna", "Jose", "Michelle", "Edward", "Carol", "Brian", "Amanda",
    "George", "Melissa", "Ronald", "Deborah"};
static const char* kLastNames[] = {"Smith", "Johnson", "Williams", "Brown",
    "Jones", "Garcia", "Miller", "Davis", "Rodriguez", "Martinez", "Hernandez",
    "Lopez", "Gonzalez", "Wilson", "Anderson", "Thomas", "Taylor", "Moore",
    "Jackson", "Martin", "Lee", "Perez", "Thompson", "White", "Harris",
    "Sanchez", "Clark", "Ramirez", "Lewis", "Robinson", "Walker", "Young",
    "Allen", "King", "Wright", "Scott", "Torres", "Nguyen", "Hill", "Flores"};
static const char* kSalutations[] = {"Mr.", "Mrs.", "Ms.", "Dr.", "Miss", "Sir"};
static const char* kCredit[] = {"Low Risk", "Good", "High Risk", "Unknown"};
static const char* kColors[] = {"red", "blue", "green", "yellow", "purple",
    "orange", "black", "white", "pink", "brown", "gray", "cyan", "magenta",
    "ivory", "khaki", "lavender", "maroon", "navy", "olive", "salmon", "tan",
    "teal", "turquoise", "violet", "beige", "azure", "chartreuse", "coral",
    "crimson", "gold", "silver", "plum", "orchid", "peach", "mint", "rose",
    "ghost", "snow", "seashell", "linen"};
static const char* kUnits[] = {"Each", "Dozen", "Case", "Pound", "Ounce",
    "Pallet", "Gross", "Box", "Carton", "Bundle", "Ton", "Dram", "Cup",
    "Gram", "Lb", "Oz", "Tbl", "Tsp", "Unknown", "N/A"};
static const char* kSizes[] = {"small", "medium", "large", "extra large",
    "economy", "petite", "N/A"};
static const char* kContainers[] = {"Unknown"};
static const char* kHours[] = {"8AM-4PM", "8AM-8AM", "8AM-12AM"};
static const char* kShipTypes[] = {"EXPRESS", "NEXT DAY", "OVERNIGHT",
    "REGULAR", "TWO DAY", "LIBRARY"};
static const char* kShipCodes[] = {"AIR", "SURFACE", "SEA"};
static const char* kShifts[] = {"first", "second", "third"};
static const char* kWordPool[] = {"results", "important", "whole", "right",
    "general", "great", "special", "large", "social", "economic", "national",
    "young", "early", "possible", "different", "small", "major", "final",
    "international", "full", "public", "available", "local", "sure", "low",
    "necessary", "true", "significant", "recent", "certain", "military",
    "central", "similar", "main", "individual", "political", "common", "strong",
    "easy", "clear", "single", "hard", "good", "new", "old", "high", "long",
    "little", "own", "other"};

template <size_t N>
static const char* pick(Rng& r, const char* const (&pool)[N]) {
  return pool[r.next() % N];
}

// Shared weighted distribution tables — generated from dists.json at
// build time (ndstpu.check.render_dists_header).  The SAME tables feed
// dsqgen-style template-parameter draws in streamgen.py, the analog of
// dsdgen and dsqgen reading the same .dst files (reference
// nds/tpcds-gen/patches/templates.patch `distmember(fips_county,...)`):
// predicates rendered into queries land on value domains the generated
// data actually has, with realistic non-uniform selectivity.
#include "dists_gen.h"

static int dpick_idx(Rng& r, const DistTable& t) {
  int64_t x = r.range(0, t.total - 1);
  for (int i = 0; i < t.n; i++) {
    x -= t.e[i].w;
    if (x < 0) return i;
  }
  return 0;
}
static const char* dpick(Rng& r, const DistTable& t) {
  return t.e[dpick_idx(r, t)].v;
}
// gmt-offset tables carry string values ("-5"); columns store ints
static int64_t dpick_int(Rng& r, const DistTable& t) {
  return atoll(dpick(r, t));
}

static std::string sentence(Rng& r, int nwords) {
  std::string s;
  for (int i = 0; i < nwords; i++) {
    if (i) s += ' ';
    s += kWordPool[r.next() % (sizeof(kWordPool) / sizeof(kWordPool[0]))];
  }
  return s;
}

// 16-char business key, unique per sk: "AAAA..." base-26 suffix of sk.
static std::string bkey(int64_t sk) {
  char b[17];
  memset(b, 'A', 16);
  b[16] = 0;
  uint64_t v = (uint64_t)sk;
  for (int i = 15; i >= 0 && v; i--) {
    b[i] = (char)('A' + (v % 26));
    v /= 26;
  }
  return std::string(b);
}

// ---------------------------------------------------------------------------
// Scaling model.  SF == gigabytes, like dsdgen -scale.  Row counts follow
// the published TPC-DS row-count step table (spec Table 3-2) at the step
// scale factors 1/10/100/1000 — the same table dsdgen's -scale implements
// (the reference wraps dsdgen at nds/tpcds-gen/src/main/java/org/notmysock/
// tpcds/GenTable.java:49-167).  The step table is NOT a smooth curve:
// item jumps 18,000 -> 102,000 at SF10, customer 100,000 -> 500,000,
// web_site is even non-monotonic (42 at SF10, 24 at SF100) — a lin/sqrt
// heuristic silently changes the workload above SF1.
// Between steps: facts interpolate linearly in SF, dims geometrically
// (log-scale across each decade); below SF1 both shrink from the SF1
// anchor (facts linear, dims damped) so tiny test datasets keep their
// proportions; above SF1000 the last segment extrapolates.
// ---------------------------------------------------------------------------

struct Sizes {
  double sf;
  int64_t store_sales, catalog_sales, web_sales;
  int64_t store_returns, catalog_returns, web_returns;
  int64_t inventory, inv_weeks;
  int64_t customer, customer_address, customer_demographics;
  int64_t household_demographics, income_band;
  int64_t item, store, warehouse, web_site, web_page, promotion, catalog_page;
  int64_t call_center, ship_mode, reason, time_dim, date_dim;
};

static int64_t lin(double sf, int64_t base) {
  int64_t v = (int64_t)llround(base * sf);
  return v < 1 ? 1 : v;
}

// one table's published row counts at SF 1 / 10 / 100 / 1000
struct Steps {
  int64_t s1, s10, s100, s1000;
};

static int64_t step_count(double sf, const Steps& t, bool fact) {
  if (sf < 1.0) {
    double f = fact ? sf : (0.1 + 0.9 * sf);
    int64_t v = (int64_t)llround((double)t.s1 * f);
    return v < 1 ? 1 : v;
  }
  const double xs[4] = {1.0, 10.0, 100.0, 1000.0};
  const double ys[4] = {(double)t.s1, (double)t.s10, (double)t.s100,
                        (double)t.s1000};
  if (sf >= 1000.0) {
    double v = fact ? ys[3] * (sf / 1000.0)
                    : ys[3] * pow(ys[3] / ys[2], log10(sf / 1000.0));
    return (int64_t)llround(v);
  }
  int i = sf < 10.0 ? 0 : (sf < 100.0 ? 1 : 2);
  double v;
  if (sf == xs[i]) {
    v = ys[i];
  } else if (fact) {
    double w = (sf - xs[i]) / (xs[i + 1] - xs[i]);
    v = ys[i] + w * (ys[i + 1] - ys[i]);
  } else {
    double w = log10(sf / xs[i]);  // 0..1 across the decade
    v = ys[i] * pow(ys[i + 1] / ys[i], w);
  }
  int64_t r = (int64_t)llround(v);
  return r < 1 ? 1 : r;
}

static Sizes compute_sizes(double sf) {
  // TPC-DS spec Table 3-2 row counts, columns SF1 / SF10 / SF100 / SF1000
  static const Steps kStoreSales = {2880404, 28800991, 287997024,
                                    2879987999};
  static const Steps kCatalogSales = {1441548, 14401261, 143997065,
                                      1439980416};
  static const Steps kWebSales = {719384, 7197566, 72001237, 720000376};
  static const Steps kStoreReturns = {287514, 2875432, 28795080,
                                      287999764};
  static const Steps kCatalogReturns = {144067, 1439749, 14404374,
                                        143996756};
  static const Steps kWebReturns = {71763, 719217, 7197670, 71997522};
  static const Steps kItem = {18000, 102000, 204000, 300000};
  static const Steps kCustomer = {100000, 500000, 2000000, 12000000};
  static const Steps kCustomerAddress = {50000, 250000, 1000000, 6000000};
  static const Steps kStore = {12, 102, 402, 1002};
  static const Steps kWarehouse = {5, 10, 15, 20};
  static const Steps kWebPage = {60, 200, 2040, 3000};
  static const Steps kPromotion = {300, 500, 1000, 1500};
  static const Steps kCallCenter = {6, 24, 30, 42};
  static const Steps kWebSite = {30, 42, 24, 54};
  static const Steps kCatalogPage = {11718, 12000, 20400, 30000};
  static const Steps kReason = {35, 45, 55, 65};
  Sizes z;
  z.sf = sf;
  z.store_sales = step_count(sf, kStoreSales, true);
  z.catalog_sales = step_count(sf, kCatalogSales, true);
  z.web_sales = step_count(sf, kWebSales, true);
  z.store_returns = step_count(sf, kStoreReturns, true);
  z.catalog_returns = step_count(sf, kCatalogReturns, true);
  z.web_returns = step_count(sf, kWebReturns, true);
  z.item = step_count(sf, kItem, false);
  z.warehouse = step_count(sf, kWarehouse, false);
  z.inv_weeks = 261;  // weekly snapshots over the 5-year window
  // inventory == weeks x (item/2) x warehouse; at the step SFs this
  // reproduces the published counts exactly (e.g. 261*51,000*10 =
  // 133,110,000 at SF10) and stays consistent with item/warehouse
  // in between
  z.inventory = z.inv_weeks * (z.item / 2 < 1 ? 1 : z.item / 2) * z.warehouse;
  z.customer = step_count(sf, kCustomer, false);
  z.customer_address = step_count(sf, kCustomerAddress, false);
  // full cross product of the demographic attributes — derived from
  // the SHARED dist tables so a dists.json edit cannot silently
  // truncate coverage (gender x marital x education x 20 purchase
  // estimates x 4 credit ratings x 7^3 dependent counts = 1,920,800
  // at the spec sizes, locked by test_spec_step_table_cardinalities)
  z.customer_demographics = (int64_t)kDist_gender.n *
      kDist_marital_status.n * kDist_education.n * 20 * 4 * 7 * 7 * 7;
  z.household_demographics = 7200;
  z.income_band = 20;
  z.store = step_count(sf, kStore, false);
  z.web_site = step_count(sf, kWebSite, false);
  z.web_page = step_count(sf, kWebPage, false);
  z.promotion = step_count(sf, kPromotion, false);
  z.catalog_page = step_count(sf, kCatalogPage, false);
  z.call_center = step_count(sf, kCallCenter, false);
  z.ship_mode = 20;
  z.reason = step_count(sf, kReason, false);
  z.time_dim = 86400;
  z.date_dim = DATE_DIM_ROWS;
  return z;
}

// table ids for RNG keying — order must stay stable forever.
enum TableId {
  T_CUSTOMER_ADDRESS = 1, T_CUSTOMER_DEMOGRAPHICS, T_DATE_DIM, T_WAREHOUSE,
  T_SHIP_MODE, T_TIME_DIM, T_REASON, T_INCOME_BAND, T_ITEM, T_STORE,
  T_CALL_CENTER, T_CUSTOMER, T_WEB_SITE, T_STORE_RETURNS,
  T_HOUSEHOLD_DEMOGRAPHICS, T_WEB_PAGE, T_PROMOTION, T_CATALOG_PAGE,
  T_INVENTORY, T_CATALOG_RETURNS, T_WEB_RETURNS, T_WEB_SALES,
  T_CATALOG_SALES, T_STORE_SALES, T_DBGEN_VERSION,
  // staging tables for -update
  T_S_PURCHASE = 40, T_S_PURCHASE_LINEITEM, T_S_CATALOG_ORDER,
  T_S_CATALOG_ORDER_LINEITEM, T_S_WEB_ORDER, T_S_WEB_ORDER_LINEITEM,
  T_S_STORE_RETURNS, T_S_CATALOG_RETURNS, T_S_WEB_RETURNS, T_S_INVENTORY,
  T_DELETE = 60, T_INVENTORY_DELETE,
  // draws per ORDER, not per row (web_order_number)
  T_WEB_ORDER_LINES = 80,
};

static uint64_t g_seed = 19620718;  // default base seed
static Sizes g_sz;

// chunk [begin, end) of n rows for child i of p
static void chunk(int64_t n, int p, int c, int64_t* b, int64_t* e) {
  int64_t per = n / p, rem = n % p;
  *b = (int64_t)(c - 1) * per + (c - 1 < rem ? c - 1 : rem);
  *e = *b + per + (c - 1 < rem ? 1 : 0);
}

// ---------------------------------------------------------------------------
// Sales row models.  Returns tables re-derive their parent sale's values by
// reconstructing the same Rng, giving exact referential integrity without
// storing anything.
// ---------------------------------------------------------------------------

struct SaleCore {
  int64_t date_sk, time_sk, item_sk, customer_sk, cdemo_sk, hdemo_sk, addr_sk;
  int64_t channel_sk;   // store_sk / call_center-ish / web_site_sk
  int64_t promo_sk, ticket;  // ticket or order number
  int64_t quantity;
  int64_t wholesale, list, sales;  // cents per unit
  int64_t ext_discount, ext_sales, ext_wholesale, ext_list, ext_tax, coupon;
  int64_t net_paid, net_paid_tax, net_profit;
  bool null_date, null_customer, null_channel, null_promo;
};

// items per ticket (avg ~3) — ticket id = row / spread (store and
// catalog; web orders have the source's structure, below)
static const int TICKET_SPREAD = 3;

// ws_order_number of web_sales row `row`: orders of 8 to 16 lines,
// uniform (dsdgen's w_web_sales.c draws 8..16 line items an order).
// Orders come in pairs that share 24 rows: the first of pair p takes
// a drawn 8..16 of them and the second the rest, so each length is
// uniform on 8..16 and any row finds its order in O(1), whatever chunk
// or returns table asks.  The draw is the pair's own
// (Rng(seed, T_WEB_ORDER_LINES, pair)), not the row's counter stream:
// every other column of a row is drawn as before.
static const int64_t WEB_ORDER_PAIR_ROWS = 24;

static int64_t web_order_number(int64_t row) {
  int64_t pair = row / WEB_ORDER_PAIR_ROWS;
  int64_t first = Rng(g_seed, T_WEB_ORDER_LINES, pair).range(8, 16);
  return 2 * pair + (row % WEB_ORDER_PAIR_ROWS >= first ? 2 : 1);
}

static SaleCore gen_sale(uint64_t table_id, int64_t row, int64_t n_channel) {
  Rng r(g_seed, table_id, row);
  SaleCore s;
  s.null_date = r.chance(0.02);
  s.date_sk = r.range(SALES_FIRST_JD, SALES_LAST_JD);
  // holiday-season date skew: ~30% of sales land in Nov/Dec (dsdgen
  // concentrates sales around the holidays the same way; uniform dates
  // starved the date-partition pruning and Q-over-December queries of
  // realistic selectivity).  Both draws always happen — the counter
  // stream must not depend on the branch (returns re-derive the sale).
  bool holiday = r.chance(0.30);
  int64_t hol_off = r.range(0, 60);
  if (holiday) {
    Civil c = civil_from_days(s.date_sk - JD_EPOCH_1970);
    int y = c.y > 2002 ? 2002 : c.y;  // Nov 2003 exceeds the window
    s.date_sk = days_from_civil(y, 11, 1) + JD_EPOCH_1970 + hol_off;
  }
  s.time_sk = r.range(0, 86399);
  s.item_sk = r.zipf(g_sz.item);
  s.null_customer = r.chance(0.03);
  s.customer_sk = r.zipf(g_sz.customer);
  s.cdemo_sk = r.range(1, g_sz.customer_demographics);
  s.hdemo_sk = r.range(1, g_sz.household_demographics);
  s.addr_sk = r.range(1, g_sz.customer_address);
  s.null_channel = r.chance(0.02);
  s.channel_sk = r.range(1, n_channel);
  s.null_promo = r.chance(0.5);
  s.promo_sk = r.range(1, g_sz.promotion);
  s.ticket = table_id == T_WEB_SALES ? web_order_number(row)
                                     : row / TICKET_SPREAD + 1;
  s.quantity = r.range(1, 100);
  s.wholesale = r.cents(100, 10000);                     // 1.00 .. 100.00
  s.list = s.wholesale + r.cents(0, s.wholesale);        // markup <= 100%
  s.sales = (s.list * r.range(20, 100)) / 100;           // discount off list
  s.ext_sales = s.quantity * s.sales;
  s.ext_wholesale = s.quantity * s.wholesale;
  s.ext_list = s.quantity * s.list;
  s.ext_discount = s.ext_list - s.ext_sales;
  s.coupon = r.chance(0.15) ? r.cents(0, s.ext_sales / 2) : 0;
  s.ext_tax = ((s.ext_sales - s.coupon) * r.range(0, 9)) / 100;
  s.net_paid = s.ext_sales - s.coupon;
  s.net_paid_tax = s.net_paid + s.ext_tax;
  s.net_profit = s.net_paid - s.ext_wholesale;
  return s;
}

// deterministic "is row k of parent sales returned" mapping: return row j
// maps to parent sale row j * (parent_n / returns_n)-ish stride.
static int64_t return_parent_row(int64_t j, int64_t parent_n, int64_t ret_n) {
  if (ret_n <= 0) return 0;
  int64_t stride = parent_n / ret_n;
  if (stride < 1) stride = 1;
  return (j * stride) % parent_n;
}

struct RetCore {
  int64_t ret_date_sk, ret_time_sk, reason_sk, qty;
  int64_t amt, tax, amt_inc_tax, fee, ship_cost, refunded, reversed, credit,
      net_loss;
};

static RetCore gen_return(uint64_t table_id, int64_t row, const SaleCore& s) {
  Rng r(g_seed, table_id, row);
  RetCore t;
  t.ret_date_sk = s.date_sk + r.range(1, 90);
  if (t.ret_date_sk > SALES_LAST_JD + 90) t.ret_date_sk = SALES_LAST_JD + 90;
  t.ret_time_sk = r.range(0, 86399);
  t.reason_sk = r.range(1, g_sz.reason);
  t.qty = r.range(1, s.quantity);
  t.amt = t.qty * s.sales;
  t.tax = (t.amt * r.range(0, 9)) / 100;
  t.amt_inc_tax = t.amt + t.tax;
  t.fee = r.cents(50, 10000);
  t.ship_cost = r.cents(0, t.amt / 2 + 1);
  t.refunded = (t.amt * r.range(0, 100)) / 100;
  int64_t rest = t.amt - t.refunded;
  t.reversed = (rest * r.range(0, 100)) / 100;
  t.credit = rest - t.reversed;
  t.net_loss = t.fee + t.ship_cost + t.tax;
  return t;
}

// ---------------------------------------------------------------------------
// Dimension generators
// ---------------------------------------------------------------------------

static void gen_customer_address(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_CUSTOMER_ADDRESS, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    char num[16];
    snprintf(num, sizeof num, "%" PRId64, r.range(1, 999));
    w.fstr(num);
    {
      std::string sn = std::string(pick(r, kStreetNames));
      if (r.chance(0.3)) sn += std::string(" ") + pick(r, kStreetNames);
      w.fstr(sn);
    }
    w.fstr(pick(r, kStreetTypes));
    if (r.chance(0.85)) {
      char suite[16];
      snprintf(suite, sizeof suite, "Suite %" PRId64, r.range(0, 99));
      w.fstr(suite);
    } else
      w.fnull();
    w.fstr(dpick(r, kDist_cities));
    w.fstr(dpick(r, kDist_fips_county));
    w.fstr(dpick(r, kDist_states));
    char zip[8];
    snprintf(zip, sizeof zip, "%05" PRId64, r.range(601, 99950));
    w.fstr(zip);
    w.fstr(kCountries[0]);
    // gmt offset, weighted toward eastern/central like the population
    w.fmoney(100 * dpick_int(r, kDist_gmt_offset));
    w.fstr(pick(r, kLocationTypes));
    w.endrow();
  }
}

static void gen_customer_demographics(Writer& w, int64_t b, int64_t e) {
  // pure cross-product enumeration like TPC-DS: gender x marital x education
  // x purchase_estimate x credit x dep x dep_employed x dep_college
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1, v = i;
    int g = v % kDist_gender.n; v /= kDist_gender.n;
    int m = v % kDist_marital_status.n; v /= kDist_marital_status.n;
    int ed = v % kDist_education.n; v /= kDist_education.n;
    int pe = v % 20; v /= 20;
    int cr = v % 4; v /= 4;
    int dep = v % 7; v /= 7;
    int depe = v % 7; v /= 7;
    int depc = v % 7;
    w.fint(sk);
    w.fstr(kDist_gender.e[g].v);
    w.fstr(kDist_marital_status.e[m].v);
    w.fstr(kDist_education.e[ed].v);
    w.fint(500 * (pe + 1));
    w.fstr(kCredit[cr]);
    w.fint(dep);
    w.fint(depe);
    w.fint(depc);
    w.endrow();
  }
}

static const char* kDayNames[] = {"Sunday", "Monday", "Tuesday", "Wednesday",
    "Thursday", "Friday", "Saturday"};

static void gen_date_dim(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t jd = DATE_DIM_FIRST_JD + i;
    int64_t days70 = jd - JD_EPOCH_1970;
    Civil c = civil_from_days(days70);
    int dow = weekday(days70);
    int64_t jan1 = days_from_civil(c.y, 1, 1);
    int doy = (int)(days70 - jan1) + 1;
    int qoy = (c.m - 1) / 3 + 1;
    int64_t month_seq = (int64_t)(c.y - 1900) * 12 + (c.m - 1);
    int64_t week_seq = (jd - DATE_DIM_FIRST_JD) / 7 + 1;
    int64_t quarter_seq = (int64_t)(c.y - 1900) * 4 + (qoy - 1);
    w.fint(jd);                    // d_date_sk
    w.fstr(bkey(jd));              // d_date_id
    w.fdate(jd);                   // d_date
    w.fint(month_seq);
    w.fint(week_seq);
    w.fint(quarter_seq);
    w.fint(c.y);
    w.fint(dow);
    w.fint(c.m);
    w.fint(c.d);
    w.fint(qoy);
    w.fint(c.y);                   // fiscal == calendar
    w.fint(quarter_seq);
    w.fint(week_seq);
    w.fstr(kDayNames[dow]);
    char qn[24];
    snprintf(qn, sizeof qn, "%04dQ%d", c.y, qoy);
    w.fstr(qn);
    w.fstr((c.m == 12 && c.d == 25) || (c.m == 1 && c.d == 1) || doy == 185 ? "Y"
                                                                            : "N");
    w.fstr(dow == 0 || dow == 6 ? "Y" : "N");
    w.fstr((c.m == 12 && c.d == 26) || (c.m == 1 && c.d == 2) ? "Y" : "N");
    int64_t first_dom = days_from_civil(c.y, c.m, 1) + JD_EPOCH_1970;
    int nm_y = c.m == 12 ? c.y + 1 : c.y;
    int nm_m = c.m == 12 ? 1 : c.m + 1;
    int64_t last_dom = days_from_civil(nm_y, nm_m, 1) + JD_EPOCH_1970 - 1;
    w.fint(first_dom);
    w.fint(last_dom);
    w.fint(jd - 365);  // same day last year
    w.fint(jd - 91);   // same day last quarter
    w.fstr("N");
    w.fstr("N");
    w.fstr("N");
    w.fstr("N");
    w.fstr("N");
    w.endrow();
  }
}

static void gen_time_dim(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i;  // t_time_sk in [0, 86399]
    int h = (int)(i / 3600), mi = (int)((i / 60) % 60), s = (int)(i % 60);
    w.fint(sk);
    w.fstr(bkey(sk + 1));
    w.fint(i);
    w.fint(h);
    w.fint(mi);
    w.fint(s);
    w.fstr(h < 12 ? "AM" : "PM");
    w.fstr(kShifts[h / 8]);
    w.fstr(kShifts[(h / 4) % 3]);
    const char* meal = h >= 6 && h <= 9    ? "breakfast"
                       : h >= 11 && h <= 14 ? "lunch"
                       : h >= 17 && h <= 21 ? "dinner"
                                            : "";
    if (*meal)
      w.fstr(meal);
    else
      w.fnull();
    w.endrow();
  }
}

static void gen_warehouse(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_WAREHOUSE, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fstr("Warehouse " + std::to_string(sk));
    w.fint(r.range(50000, 999999));
    char num[16];
    snprintf(num, sizeof num, "%" PRId64, r.range(1, 999));
    w.fstr(num);
    w.fstr(pick(r, kStreetNames));
    w.fstr(pick(r, kStreetTypes));
    char suite[16];
    snprintf(suite, sizeof suite, "Suite %" PRId64, r.range(0, 99));
    w.fstr(suite);
    w.fstr(dpick(r, kDist_cities));
    w.fstr(dpick(r, kDist_fips_county));
    w.fstr(dpick(r, kDist_states));
    char zip[8];
    snprintf(zip, sizeof zip, "%05" PRId64, r.range(601, 99950));
    w.fstr(zip);
    w.fstr(kCountries[0]);
    w.fmoney(-100 * r.range(5, 10));
    w.endrow();
  }
}

static void gen_ship_mode(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_SHIP_MODE, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fstr(kShipTypes[i % 6]);
    w.fstr(kShipCodes[(i / 6) % 3]);
    w.fstr(kDist_carriers.e[i % kDist_carriers.n].v);
    char contract[24];
    snprintf(contract, sizeof contract, "%" PRId64, r.range(1000000, 9999999));
    w.fstr(contract);
    w.endrow();
  }
}

static void gen_reason(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fstr(kDist_reasons.e[i % kDist_reasons.n].v);
    w.endrow();
  }
}

static void gen_income_band(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    w.fint(sk);
    w.fint(i * 10000 + 1 - (i == 0));
    w.fint((i + 1) * 10000);
    w.endrow();
  }
}

static void gen_item(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_ITEM, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fdate(SALES_FIRST_JD - (int64_t)r.range(0, 1000));  // rec start
    if (r.chance(0.25))
      w.fdate(SALES_LAST_JD + (int64_t)r.range(0, 200));
    else
      w.fnull();
    w.fstr(sentence(r, (int)r.range(5, 20)));
    int64_t price = r.cents(100, 10000);
    w.fmoney(price);
    w.fmoney((price * r.range(30, 90)) / 100);
    // weighted category/class: hot categories get more items, so
    // Zipf-hot item keys skew category aggregates realistically (the
    // dist indices also feed the brand-id encoding below)
    int cat = dpick_idx(r, kDist_categories);
    int cls = dpick_idx(r, kDist_classes);
    int brand = (int)(r.range(1, 10));
    int64_t brand_id = (cat + 1) * 1000000 + (cls + 1) * 1000 + brand;
    w.fint(brand_id);
    {
      char bn[40];
      snprintf(bn, sizeof bn, "%s #%d", kDist_classes.e[cls].v, brand);
      w.fstr(bn);  // i_brand
    }
    w.fint(cls + 1);
    w.fstr(kDist_classes.e[cls].v);
    w.fint(cat + 1);
    w.fstr(kDist_categories.e[cat].v);
    int64_t manu = r.range(1, 1000);
    w.fint(manu);
    {
      char mn[24];
      snprintf(mn, sizeof mn, "manu#%" PRId64, manu);
      w.fstr(mn);
    }
    w.fstr(pick(r, kSizes));
    w.fstr(sentence(r, 2));  // formulation
    w.fstr(dpick(r, kDist_colors));
    w.fstr(pick(r, kUnits));
    w.fstr(kContainers[0]);
    w.fint(r.range(1, 100));
    {
      char pn[32];
      snprintf(pn, sizeof pn, "%s%" PRId64, pick(r, kColors), sk);
      w.fstr(pn);  // i_product_name
    }
    w.endrow();
  }
}

static void gen_store(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_STORE, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fdate(SALES_FIRST_JD - (int64_t)r.range(100, 2000));
    w.fnull();  // rec_end_date
    if (r.chance(0.1))
      w.fint(r.range(SALES_FIRST_JD, SALES_LAST_JD));
    else
      w.fnull();  // closed_date_sk
    w.fstr(std::string(pick(r, kLastNames)) + " Store");
    w.fint(r.range(200, 300));
    w.fint(r.range(5000000, 9999999));
    w.fstr(kHours[i % 3]);
    w.fstr(std::string(pick(r, kFirstNames)) + " " + pick(r, kLastNames));
    w.fint(r.range(1, 10));
    w.fstr(sentence(r, 6));
    w.fstr(sentence(r, 10));
    w.fstr(std::string(pick(r, kFirstNames)) + " " + pick(r, kLastNames));
    w.fint(r.range(1, 2));
    w.fstr("Division " + std::to_string(r.range(1, 2)));
    w.fint(r.range(1, 2));
    w.fstr("Company " + std::to_string(r.range(1, 2)));
    char num[16];
    snprintf(num, sizeof num, "%" PRId64, r.range(1, 999));
    w.fstr(num);
    w.fstr(pick(r, kStreetNames));
    w.fstr(pick(r, kStreetTypes));
    char suite[16];
    snprintf(suite, sizeof suite, "Suite %" PRId64, r.range(0, 99));
    w.fstr(suite);
    // stores draw from the small CONDITIONED pools (store_cities /
    // store_states / store_gmt): with only 12 stores at SF1, template
    // parameters predicating on s_city/s_state must share the exact
    // domain stores are assigned from or they match zero rows
    w.fstr(dpick(r, kDist_store_cities));
    w.fstr(dpick(r, kDist_fips_county));
    w.fstr(dpick(r, kDist_store_states));
    char zip[8];
    snprintf(zip, sizeof zip, "%05" PRId64, r.range(601, 99950));
    w.fstr(zip);
    w.fstr(kCountries[0]);
    w.fmoney(100 * dpick_int(r, kDist_store_gmt));
    w.fmoney(r.range(0, 11));  // tax percentage 0.00-0.11
    w.endrow();
  }
}

static void gen_call_center(Writer& w, int64_t b, int64_t e) {
  static const char* kCCNames[] = {"NY Metro", "Mid Atlantic", "Pacific NW",
      "North Midwest", "California", "New England", "Southeast", "Southwest",
      "Hawaii/Alaska", "Central", "Mountain", "Plains"};
  static const char* kCCClass[] = {"small", "medium", "large"};
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_CALL_CENTER, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fdate(SALES_FIRST_JD - (int64_t)r.range(100, 2000));
    w.fnull();
    w.fnull();  // closed_date_sk
    w.fint(SALES_FIRST_JD - (int64_t)r.range(100, 2000));  // open_date_sk
    w.fstr(kCCNames[i % 12]);
    w.fstr(kCCClass[i % 3]);
    w.fint(r.range(100, 700));
    w.fint(r.range(10000, 40000));
    w.fstr(kHours[i % 3]);
    w.fstr(std::string(pick(r, kFirstNames)) + " " + pick(r, kLastNames));
    w.fint(r.range(1, 6));
    w.fstr(sentence(r, 3));
    w.fstr(sentence(r, 8));
    w.fstr(std::string(pick(r, kFirstNames)) + " " + pick(r, kLastNames));
    w.fint(r.range(1, 2));
    w.fstr("Division " + std::to_string(r.range(1, 2)));
    w.fint(r.range(1, 6));
    w.fstr("Company " + std::to_string(r.range(1, 6)));
    char num[16];
    snprintf(num, sizeof num, "%" PRId64, r.range(1, 999));
    w.fstr(num);
    w.fstr(pick(r, kStreetNames));
    w.fstr(pick(r, kStreetTypes));
    char suite[16];
    snprintf(suite, sizeof suite, "Suite %" PRId64, r.range(0, 99));
    w.fstr(suite);
    w.fstr(dpick(r, kDist_cities));
    w.fstr(dpick(r, kDist_fips_county));
    w.fstr(dpick(r, kDist_states));
    char zip[8];
    snprintf(zip, sizeof zip, "%05" PRId64, r.range(601, 99950));
    w.fstr(zip);
    w.fstr(kCountries[0]);
    w.fmoney(-100 * r.range(5, 10));
    w.fmoney(r.range(0, 11));
    w.endrow();
  }
}

static void gen_customer(Writer& w, int64_t b, int64_t e) {
  static const char* kBirthCountries[] = {"UNITED STATES", "CANADA", "MEXICO",
      "GERMANY", "FRANCE", "JAPAN", "CHINA", "INDIA", "BRAZIL", "ITALY",
      "NETHERLANDS", "PORTUGAL", "IRELAND", "GREECE", "TURKEY", "NIGERIA",
      "KENYA", "EGYPT", "PERU", "CHILE"};
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_CUSTOMER, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    if (r.chance(0.96)) w.fint(r.range(1, g_sz.customer_demographics)); else w.fnull();
    if (r.chance(0.96)) w.fint(r.range(1, g_sz.household_demographics)); else w.fnull();
    if (r.chance(0.96)) w.fint(r.range(1, g_sz.customer_address)); else w.fnull();
    int64_t first_sale = r.range(SALES_FIRST_JD - 1000, SALES_LAST_JD);
    w.fint(first_sale + r.range(0, 30));  // first shipto
    w.fint(first_sale);                   // first sales
    w.fstr(pick(r, kSalutations));
    const char* fn = pick(r, kFirstNames);
    w.fstr(fn);
    const char* ln = pick(r, kLastNames);
    w.fstr(ln);
    w.fstr(r.chance(0.5) ? "Y" : "N");
    w.fint(r.range(1, 28));
    w.fint(r.range(1, 12));
    w.fint(r.range(1924, 1992));
    w.fstr(kBirthCountries[r.next() % 20]);
    w.fnull();  // c_login
    {
      char email[80];
      snprintf(email, sizeof email, "%s.%s@example.com", fn, ln);
      w.fstr(email);
    }
    w.fint(r.range(SALES_LAST_JD - 400, SALES_LAST_JD));
    w.endrow();
  }
}

static void gen_web_site(Writer& w, int64_t b, int64_t e) {
  static const char* kSiteNames[] = {"site_0", "site_1", "site_2", "site_3",
      "site_4", "site_5"};
  // dsdgen's web_company_name: the "syllables" word of a company id
  // 1..6 -- the domain query94 / query95 draw their literal 'pri' from
  static const char* kCompanyNames[] = {"ought", "able", "pri", "ese",
      "anti", "cally"};
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_WEB_SITE, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fdate(SALES_FIRST_JD - (int64_t)r.range(100, 2000));
    w.fnull();
    w.fstr(kSiteNames[i % 6]);
    w.fint(SALES_FIRST_JD - (int64_t)r.range(100, 2000));
    w.fnull();  // close date
    w.fstr(sentence(r, 2));
    w.fstr(std::string(pick(r, kFirstNames)) + " " + pick(r, kLastNames));
    w.fint(r.range(1, 6));
    w.fstr(sentence(r, 3));
    w.fstr(sentence(r, 8));
    w.fstr(std::string(pick(r, kFirstNames)) + " " + pick(r, kLastNames));
    w.fint(r.range(1, 2));
    w.fstr(kCompanyNames[r.range(1, 6) - 1]);
    char num[16];
    snprintf(num, sizeof num, "%" PRId64, r.range(1, 999));
    w.fstr(num);
    w.fstr(pick(r, kStreetNames));
    w.fstr(pick(r, kStreetTypes));
    char suite[16];
    snprintf(suite, sizeof suite, "Suite %" PRId64, r.range(0, 99));
    w.fstr(suite);
    w.fstr(dpick(r, kDist_cities));
    w.fstr(dpick(r, kDist_fips_county));
    w.fstr(dpick(r, kDist_states));
    char zip[8];
    snprintf(zip, sizeof zip, "%05" PRId64, r.range(601, 99950));
    w.fstr(zip);
    w.fstr(kCountries[0]);
    w.fmoney(-100 * r.range(5, 10));
    w.fmoney(r.range(0, 11));
    w.endrow();
  }
}

static void gen_household_demographics(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1, v = i;
    int ib = v % 20; v /= 20;
    int bp = v % kDist_buy_potential.n; v /= kDist_buy_potential.n;
    int dep = v % 10; v /= 10;
    int veh = v % 6;
    w.fint(sk);
    w.fint(ib + 1);
    w.fstr(kDist_buy_potential.e[bp].v);
    w.fint(dep);
    w.fint(veh - 1 + 1);
    w.endrow();
  }
}

static void gen_web_page(Writer& w, int64_t b, int64_t e) {
  static const char* kPageTypes[] = {"ad", "dynamic", "feedback", "general",
      "order", "protected", "welcome"};
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_WEB_PAGE, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    w.fdate(SALES_FIRST_JD - (int64_t)r.range(100, 2000));
    w.fnull();
    w.fint(SALES_FIRST_JD - (int64_t)r.range(0, 1000));
    w.fint(SALES_FIRST_JD + (int64_t)r.range(0, 1000));
    w.fstr(r.chance(0.3) ? "Y" : "N");
    if (r.chance(0.2)) w.fint(r.range(1, g_sz.customer)); else w.fnull();
    w.fstr("http://www.example.com/page_" + std::to_string(sk));
    w.fstr(kPageTypes[i % 7]);
    w.fint(r.range(100, 7000));
    w.fint(r.range(2, 25));
    w.fint(r.range(1, 7));
    w.fint(r.range(0, 4));
    w.endrow();
  }
}

static void gen_promotion(Writer& w, int64_t b, int64_t e) {
  static const char* kPurpose[] = {"Unknown", "ad", "discount", "coupon"};
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_PROMOTION, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    int64_t start = r.range(SALES_FIRST_JD, SALES_LAST_JD - 60);
    w.fint(start);
    w.fint(start + r.range(10, 60));
    w.fint(r.range(1, g_sz.item));
    w.fmoney(100000);  // p_cost 1000.00
    w.fint(r.range(1, 3));
    {
      char pn[24];
      snprintf(pn, sizeof pn, "promo_%" PRId64, sk);
      w.fstr(pn);
    }
    for (int c = 0; c < 8; c++) w.fstr(r.chance(0.5) ? "Y" : "N");
    w.fstr(sentence(r, 5));
    w.fstr(kPurpose[i % 4]);
    w.fstr(r.chance(0.5) ? "Y" : "N");
    w.endrow();
  }
}

static void gen_catalog_page(Writer& w, int64_t b, int64_t e) {
  static const char* kCpTypes[] = {"bi-annual", "quarterly", "monthly"};
  for (int64_t i = b; i < e; i++) {
    int64_t sk = i + 1;
    Rng r(g_seed, T_CATALOG_PAGE, i);
    w.fint(sk);
    w.fstr(bkey(sk));
    int64_t start = SALES_FIRST_JD + (i / 108) * 30;
    w.fint(start);
    w.fint(start + 90);
    w.fstr("DEPARTMENT");
    w.fint(i / 108 + 1);
    w.fint(i % 108 + 1);
    w.fstr(sentence(r, 8));
    w.fstr(kCpTypes[i % 3]);
    w.endrow();
  }
}

static void gen_inventory(Writer& w, int64_t b, int64_t e) {
  int64_t items = g_sz.item / 2 < 1 ? 1 : g_sz.item / 2;
  int64_t wh = g_sz.warehouse;
  for (int64_t i = b; i < e; i++) {
    Rng r(g_seed, T_INVENTORY, i);
    int64_t week = i / (items * wh);
    int64_t rem = i % (items * wh);
    int64_t item = (rem / wh) * 2 + 1;  // every other item is stocked
    int64_t warehouse = rem % wh + 1;
    w.fint(SALES_FIRST_JD - 7 + week * 7);  // weekly date_sk
    w.fint(item);
    w.fint(warehouse);
    if (r.chance(0.05))
      w.fnull();
    else
      w.fint(r.range(0, 1000));
    w.endrow();
  }
}

static void gen_dbgen_version(Writer& w, int64_t b, int64_t e) {
  (void)b; (void)e;
  w.fstr("ndsgen-1.0");
  w.fdate(SALES_LAST_JD);
  w.fstr("00:00:00");
  w.fstr("-scale");
  w.endrow();
}

// ---------------------------------------------------------------------------
// Fact generators
// ---------------------------------------------------------------------------

static void gen_store_sales(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    SaleCore s = gen_sale(T_STORE_SALES, i, g_sz.store);
    if (s.null_date) w.fnull(); else w.fint(s.date_sk);
    w.fint(s.time_sk);
    w.fint(s.item_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    if (s.null_channel) w.fnull(); else w.fint(s.channel_sk);
    if (s.null_promo) w.fnull(); else w.fint(s.promo_sk);
    w.fint(s.ticket);
    w.fint(s.quantity);
    w.fmoney(s.wholesale);
    w.fmoney(s.list);
    w.fmoney(s.sales);
    w.fmoney(s.ext_discount);
    w.fmoney(s.ext_sales);
    w.fmoney(s.ext_wholesale);
    w.fmoney(s.ext_list);
    w.fmoney(s.ext_tax);
    w.fmoney(s.coupon);
    w.fmoney(s.net_paid);
    w.fmoney(s.net_paid_tax);
    w.fmoney(s.net_profit);
    w.endrow();
  }
}

static void gen_catalog_sales(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    SaleCore s = gen_sale(T_CATALOG_SALES, i, g_sz.call_center);
    Rng r2(g_seed, T_CATALOG_SALES + 100, i);  // extra columns stream
    int64_t ship_date = s.date_sk + r2.range(2, 120);
    if (s.null_date) w.fnull(); else w.fint(s.date_sk);
    w.fint(s.time_sk);
    w.fint(ship_date);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    // ship-to: usually same customer
    int64_t ship_cust = r2.chance(0.85) ? s.customer_sk
                                        : r2.range(1, g_sz.customer);
    if (s.null_customer) w.fnull(); else w.fint(ship_cust);
    w.fint(r2.range(1, g_sz.customer_demographics));
    w.fint(r2.range(1, g_sz.household_demographics));
    w.fint(r2.range(1, g_sz.customer_address));
    if (s.null_channel) w.fnull(); else w.fint(s.channel_sk);
    w.fint(r2.range(1, g_sz.catalog_page));
    w.fint(r2.range(1, g_sz.ship_mode));
    w.fint(r2.range(1, g_sz.warehouse));
    w.fint(s.item_sk);
    if (s.null_promo) w.fnull(); else w.fint(s.promo_sk);
    w.fint(s.ticket);  // cs_order_number
    w.fint(s.quantity);
    w.fmoney(s.wholesale);
    w.fmoney(s.list);
    w.fmoney(s.sales);
    w.fmoney(s.ext_discount);
    w.fmoney(s.ext_sales);
    w.fmoney(s.ext_wholesale);
    w.fmoney(s.ext_list);
    w.fmoney(s.ext_tax);
    w.fmoney(s.coupon);
    int64_t ship_cost = (s.ext_list * r2.range(0, 50)) / 1000;
    w.fmoney(ship_cost);
    w.fmoney(s.net_paid);
    w.fmoney(s.net_paid_tax);
    w.fmoney(s.net_paid + ship_cost);
    w.fmoney(s.net_paid_tax + ship_cost);
    w.fmoney(s.net_profit);
    w.endrow();
  }
}

static void gen_web_sales(Writer& w, int64_t b, int64_t e) {
  for (int64_t i = b; i < e; i++) {
    SaleCore s = gen_sale(T_WEB_SALES, i, g_sz.web_site);
    Rng r2(g_seed, T_WEB_SALES + 100, i);
    int64_t ship_date = s.date_sk + r2.range(2, 120);
    if (s.null_date) w.fnull(); else w.fint(s.date_sk);
    w.fint(s.time_sk);
    w.fint(ship_date);
    w.fint(s.item_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    int64_t ship_cust = r2.chance(0.85) ? s.customer_sk
                                        : r2.range(1, g_sz.customer);
    if (s.null_customer) w.fnull(); else w.fint(ship_cust);
    w.fint(r2.range(1, g_sz.customer_demographics));
    w.fint(r2.range(1, g_sz.household_demographics));
    w.fint(r2.range(1, g_sz.customer_address));
    w.fint(r2.range(1, g_sz.web_page));
    if (s.null_channel) w.fnull(); else w.fint(s.channel_sk);
    w.fint(r2.range(1, g_sz.ship_mode));
    w.fint(r2.range(1, g_sz.warehouse));
    if (s.null_promo) w.fnull(); else w.fint(s.promo_sk);
    w.fint(s.ticket);  // ws_order_number
    w.fint(s.quantity);
    w.fmoney(s.wholesale);
    w.fmoney(s.list);
    w.fmoney(s.sales);
    w.fmoney(s.ext_discount);
    w.fmoney(s.ext_sales);
    w.fmoney(s.ext_wholesale);
    w.fmoney(s.ext_list);
    w.fmoney(s.ext_tax);
    w.fmoney(s.coupon);
    int64_t ship_cost = (s.ext_list * r2.range(0, 50)) / 1000;
    w.fmoney(ship_cost);
    w.fmoney(s.net_paid);
    w.fmoney(s.net_paid_tax);
    w.fmoney(s.net_paid + ship_cost);
    w.fmoney(s.net_paid_tax + ship_cost);
    w.fmoney(s.net_profit);
    w.endrow();
  }
}

static void gen_store_returns(Writer& w, int64_t b, int64_t e) {
  for (int64_t j = b; j < e; j++) {
    int64_t i = return_parent_row(j, g_sz.store_sales, g_sz.store_returns);
    SaleCore s = gen_sale(T_STORE_SALES, i, g_sz.store);
    RetCore t = gen_return(T_STORE_RETURNS, j, s);
    w.fint(t.ret_date_sk);
    w.fint(t.ret_time_sk);
    w.fint(s.item_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    if (s.null_channel) w.fnull(); else w.fint(s.channel_sk);
    w.fint(t.reason_sk);
    w.fint(s.ticket);
    w.fint(t.qty);
    w.fmoney(t.amt);
    w.fmoney(t.tax);
    w.fmoney(t.amt_inc_tax);
    w.fmoney(t.fee);
    w.fmoney(t.ship_cost);
    w.fmoney(t.refunded);
    w.fmoney(t.reversed);
    w.fmoney(t.credit);
    w.fmoney(t.net_loss);
    w.endrow();
  }
}

static void gen_catalog_returns(Writer& w, int64_t b, int64_t e) {
  for (int64_t j = b; j < e; j++) {
    int64_t i = return_parent_row(j, g_sz.catalog_sales, g_sz.catalog_returns);
    SaleCore s = gen_sale(T_CATALOG_SALES, i, g_sz.call_center);
    Rng r2(g_seed, T_CATALOG_SALES + 100, i);
    RetCore t = gen_return(T_CATALOG_RETURNS, j, s);
    w.fint(t.ret_date_sk);
    w.fint(t.ret_time_sk);
    w.fint(s.item_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);  // returning =
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    if (s.null_channel) w.fnull(); else w.fint(s.channel_sk);
    w.fint(r2.range(1, g_sz.catalog_page));
    w.fint(r2.range(1, g_sz.ship_mode));
    w.fint(r2.range(1, g_sz.warehouse));
    w.fint(t.reason_sk);
    w.fint(s.ticket);
    w.fint(t.qty);
    w.fmoney(t.amt);
    w.fmoney(t.tax);
    w.fmoney(t.amt_inc_tax);
    w.fmoney(t.fee);
    w.fmoney(t.ship_cost);
    w.fmoney(t.refunded);
    w.fmoney(t.reversed);
    w.fmoney(t.credit);
    w.fmoney(t.net_loss);
    w.endrow();
  }
}

static void gen_web_returns(Writer& w, int64_t b, int64_t e) {
  for (int64_t j = b; j < e; j++) {
    int64_t i = return_parent_row(j, g_sz.web_sales, g_sz.web_returns);
    SaleCore s = gen_sale(T_WEB_SALES, i, g_sz.web_site);
    Rng r2(g_seed, T_WEB_SALES + 100, i);
    RetCore t = gen_return(T_WEB_RETURNS, j, s);
    w.fint(t.ret_date_sk);
    w.fint(t.ret_time_sk);
    w.fint(s.item_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    if (s.null_customer) w.fnull(); else w.fint(s.customer_sk);
    w.fint(s.cdemo_sk);
    w.fint(s.hdemo_sk);
    w.fint(s.addr_sk);
    w.fint(r2.range(1, g_sz.web_page));
    w.fint(t.reason_sk);
    w.fint(s.ticket);
    w.fint(t.qty);
    w.fmoney(t.amt);
    w.fmoney(t.tax);
    w.fmoney(t.amt_inc_tax);
    w.fmoney(t.fee);
    w.fmoney(t.ship_cost);
    w.fmoney(t.refunded);
    w.fmoney(t.reversed);
    w.fmoney(t.credit);
    w.fmoney(t.net_loss);
    w.endrow();
  }
}

// ---------------------------------------------------------------------------
// Refresh ("update") set generators — staging tables for data maintenance
// plus the delete/inventory_delete date-range tables
// (reference: nds_gen_data.py:70-83,119-127; data_maintenance/*.sql).
// ---------------------------------------------------------------------------

static void fdate10(Writer& w, int64_t jd) {  // char(10) date for staging
  Civil c = civil_from_days(jd - JD_EPOCH_1970);
  char b[16];
  snprintf(b, sizeof b, "%04d-%02d-%02d", c.y, c.m, c.d);
  w.fstr(b);
}

// the k-th update set covers a 1-month slice after the sales window
static void update_window(int update, int64_t* lo, int64_t* hi) {
  *lo = SALES_LAST_JD + 1 + (int64_t)(update - 1) * 30;
  *hi = *lo + 29;
}

static void gen_s_purchase(Writer& w, int update, int64_t b, int64_t e) {
  int64_t lo, hi;
  update_window(update, &lo, &hi);
  for (int64_t i = b; i < e; i++) {
    Rng r(g_seed + update, T_S_PURCHASE, i);
    w.fint(i + 1);
    w.fstr(bkey(r.range(1, g_sz.store)));
    w.fstr(bkey(r.range(1, g_sz.customer)));
    fdate10(w, r.range(lo, hi));
    w.fint(r.range(0, 86399));
    w.fint(r.range(1, 1000));
    w.fint(r.range(1, 1000));
    w.fstr(sentence(r, 6));
    w.endrow();
  }
}

static void gen_s_lineitems(Writer& w, uint64_t tid, int update, int64_t b,
                            int64_t e, int per_order, bool catalog, bool web) {
  for (int64_t o = b; o < e; o++) {
    for (int li = 1; li <= per_order; li++) {
      Rng r(g_seed + update, tid, o * 100 + li);
      w.fint(o + 1);
      w.fint(li);
      w.fstr(bkey(r.range(1, g_sz.item)));
      if (r.chance(0.5)) w.fstr(bkey(r.range(1, g_sz.promotion))); else w.fnull();
      w.fint(r.range(1, 100));
      w.fmoney(r.cents(100, 10000));
      w.fmoney(r.chance(0.15) ? r.cents(0, 5000) : 0);
      if (catalog || web) {
        int64_t lo, hi;
        update_window(update, &lo, &hi);
        w.fstr(bkey(r.range(1, g_sz.warehouse)));
        fdate10(w, r.range(lo, hi));
        if (catalog) {
          w.fint(r.range(1, 109));
          w.fint(r.range(1, 108));
        }
        w.fmoney(r.cents(0, 5000));
        if (web) w.fstr(bkey(r.range(1, g_sz.web_page)));
      } else {
        w.fstr(sentence(r, 4));  // plin_comment
      }
      w.endrow();
    }
  }
}

static void gen_s_order(Writer& w, uint64_t tid, int update, int64_t b,
                        int64_t e, bool web) {
  int64_t lo, hi;
  update_window(update, &lo, &hi);
  for (int64_t i = b; i < e; i++) {
    Rng r(g_seed + update, tid, i);
    w.fint(i + 1);
    w.fstr(bkey(r.range(1, g_sz.customer)));
    w.fstr(bkey(r.range(1, g_sz.customer)));
    fdate10(w, r.range(lo, hi));
    w.fint(r.range(0, 86399));
    w.fstr(bkey(r.range(1, g_sz.ship_mode)));
    w.fstr(bkey(web ? r.range(1, g_sz.web_site) : r.range(1, g_sz.call_center)));
    w.fstr(sentence(r, 6));
    w.endrow();
  }
}

static void gen_s_returns(Writer& w, uint64_t tid, int update, int64_t b,
                          int64_t e, int kind) {  // 0=store 1=catalog 2=web
  int64_t lo, hi;
  update_window(update, &lo, &hi);
  for (int64_t i = b; i < e; i++) {
    Rng r(g_seed + update, tid, i);
    int64_t amt = r.cents(100, 20000);
    int64_t tax = amt / 10;
    if (kind == 0) {
      w.fstr(bkey(r.range(1, g_sz.store)));
      w.fstr(bkey(i + 1));  // purchase id
      w.fint(r.range(1, 10));
      w.fstr(bkey(r.range(1, g_sz.item)));
      w.fstr(bkey(r.range(1, g_sz.customer)));
      fdate10(w, r.range(lo, hi));
      w.fstr("12:00:00");
      w.fint(r.range(1, g_sz.store_sales / TICKET_SPREAD + 1));
      w.fint(r.range(1, 50));
      w.fmoney(amt); w.fmoney(tax); w.fmoney(r.cents(50, 5000));
      w.fmoney(r.cents(0, 5000)); w.fmoney(amt / 2); w.fmoney(amt / 4);
      w.fmoney(amt / 4);
      w.fstr(bkey(r.range(1, g_sz.reason)));
    } else if (kind == 1) {
      w.fstr(bkey(r.range(1, g_sz.call_center)));
      w.fint(i + 1);
      w.fint(r.range(1, 10));
      w.fstr(bkey(r.range(1, g_sz.item)));
      w.fstr(bkey(r.range(1, g_sz.customer)));
      w.fstr(bkey(r.range(1, g_sz.customer)));
      fdate10(w, r.range(lo, hi));
      w.fstr("12:00:00");
      w.fint(r.range(1, 50));
      w.fmoney(amt); w.fmoney(tax); w.fmoney(r.cents(50, 5000));
      w.fmoney(r.cents(0, 5000)); w.fmoney(amt / 2); w.fmoney(amt / 4);
      w.fmoney(amt / 4);
      w.fstr(bkey(r.range(1, g_sz.reason)));
      w.fstr(bkey(r.range(1, g_sz.ship_mode)));
      w.fstr(bkey(r.range(1, g_sz.catalog_page)));
      w.fstr(bkey(r.range(1, g_sz.warehouse)));
    } else {
      w.fstr(bkey(r.range(1, g_sz.web_page)));
      w.fint(i + 1);
      w.fint(r.range(1, 10));
      w.fstr(bkey(r.range(1, g_sz.item)));
      w.fstr(bkey(r.range(1, g_sz.customer)));
      w.fstr(bkey(r.range(1, g_sz.customer)));
      fdate10(w, r.range(lo, hi));
      w.fstr("12:00:00");
      w.fint(r.range(1, 50));
      w.fmoney(amt); w.fmoney(tax); w.fmoney(r.cents(50, 5000));
      w.fmoney(r.cents(0, 5000)); w.fmoney(amt / 2); w.fmoney(amt / 4);
      w.fmoney(amt / 4);
      w.fstr(bkey(r.range(1, g_sz.reason)));
    }
    w.endrow();
  }
}

static void gen_s_inventory(Writer& w, int update, int64_t b, int64_t e) {
  int64_t lo, hi;
  update_window(update, &lo, &hi);
  for (int64_t i = b; i < e; i++) {
    Rng r(g_seed + update, T_S_INVENTORY, i);
    w.fstr(bkey(r.range(1, g_sz.warehouse)));
    w.fstr(bkey(r.range(1, g_sz.item)));
    fdate10(w, lo + (i % 4) * 7);
    w.fint(r.range(0, 1000));
    w.endrow();
  }
}

static void gen_delete_table(Writer& w, uint64_t tid, int update) {
  // 3 (date1, date2) ranges inside the historical sales window; DM delete
  // functions remove facts whose date_sk falls between them.
  for (int64_t i = 0; i < 3; i++) {
    Rng r(g_seed + update, tid, i);
    int64_t span = (SALES_LAST_JD - SALES_FIRST_JD) / 20;
    int64_t lo = SALES_FIRST_JD + (int64_t)(r.next() % (uint64_t)(SALES_LAST_JD -
                                                        SALES_FIRST_JD - span));
    fdate10(w, lo);
    fdate10(w, lo + span);
    w.endrow();
  }
}

// ---------------------------------------------------------------------------
// main
// ---------------------------------------------------------------------------

struct TableDef {
  const char* name;
  void (*gen)(Writer&, int64_t, int64_t);
  int64_t Sizes::*count;
};

int main(int argc, char** argv) {
  double sf = 1.0;
  std::string dir = ".";
  std::string only_table;
  int parallel = 1, child = 1, update = 0;
  for (int i = 1; i < argc; i++) {
    std::string a = argv[i];
    auto need = [&](const char* what) -> const char* {
      if (i + 1 >= argc) {
        fprintf(stderr, "ndsgen: %s needs a value\n", what);
        exit(2);
      }
      return argv[++i];
    };
    if (a == "-scale") sf = atof(need("-scale"));
    else if (a == "-sizes") {
      // print the scaling model for a scale factor (no generation) —
      // lets tests lock the spec step-table counts cheaply
      Sizes s = compute_sizes(atof(need("-sizes")));
      printf("store_sales|%lld\n", (long long)s.store_sales);
      printf("catalog_sales|%lld\n", (long long)s.catalog_sales);
      printf("web_sales|%lld\n", (long long)s.web_sales);
      printf("store_returns|%lld\n", (long long)s.store_returns);
      printf("catalog_returns|%lld\n", (long long)s.catalog_returns);
      printf("web_returns|%lld\n", (long long)s.web_returns);
      printf("inventory|%lld\n", (long long)s.inventory);
      printf("item|%lld\n", (long long)s.item);
      printf("customer|%lld\n", (long long)s.customer);
      printf("customer_address|%lld\n", (long long)s.customer_address);
      printf("customer_demographics|%lld\n",
             (long long)s.customer_demographics);
      printf("household_demographics|%lld\n",
             (long long)s.household_demographics);
      printf("income_band|%lld\n", (long long)s.income_band);
      printf("store|%lld\n", (long long)s.store);
      printf("warehouse|%lld\n", (long long)s.warehouse);
      printf("web_site|%lld\n", (long long)s.web_site);
      printf("web_page|%lld\n", (long long)s.web_page);
      printf("promotion|%lld\n", (long long)s.promotion);
      printf("catalog_page|%lld\n", (long long)s.catalog_page);
      printf("call_center|%lld\n", (long long)s.call_center);
      printf("ship_mode|%lld\n", (long long)s.ship_mode);
      printf("reason|%lld\n", (long long)s.reason);
      printf("time_dim|%lld\n", (long long)s.time_dim);
      printf("date_dim|%lld\n", (long long)s.date_dim);
      return 0;
    }
    else if (a == "-dir") dir = need("-dir");
    else if (a == "-table") only_table = need("-table");
    else if (a == "-parallel") parallel = atoi(need("-parallel"));
    else if (a == "-child") child = atoi(need("-child"));
    else if (a == "-update") update = atoi(need("-update"));
    else if (a == "-seed") g_seed = (uint64_t)atoll(need("-seed"));
    else if (a == "-h" || a == "--help") {
      printf("usage: ndsgen -scale SF -dir DIR [-parallel N -child I] "
             "[-table T] [-update K] [-seed S] | -sizes SF\n"
             "  -sizes SF  print the row-count scaling model (spec step "
             "table) and exit\n");
      return 0;
    } else {
      fprintf(stderr, "ndsgen: unknown arg %s\n", a.c_str());
      return 2;
    }
  }
  if (parallel < 1 || child < 1 || child > parallel) {
    fprintf(stderr, "ndsgen: bad -parallel/-child\n");
    return 2;
  }
  g_sz = compute_sizes(sf);

  char suffix[64];
  snprintf(suffix, sizeof suffix, "_%d_%d.dat", child, parallel);

  if (update > 0) {
    // refresh set sizing: proportional to SF, small.  Each job's natural
    // unit count (rows or orders) is chunked across -parallel children so
    // the driver's fan-out never duplicates content.
    int64_t orders = lin(sf, 1500);
    auto at_least_1 = [](int64_t n) { return n < 1 ? 1 : n; };
    struct {
      const char* name;
      int which;
      int64_t n;
    } jobs[] = {{"s_purchase", 0, orders},
                {"s_purchase_lineitem", 1, orders},
                {"s_catalog_order", 2, at_least_1(orders / 2)},
                {"s_catalog_order_lineitem", 3, at_least_1(orders / 2)},
                {"s_web_order", 4, at_least_1(orders / 3)},
                {"s_web_order_lineitem", 5, at_least_1(orders / 3)},
                {"s_store_returns", 6, at_least_1(orders / 5)},
                {"s_catalog_returns", 7, at_least_1(orders / 8)},
                {"s_web_returns", 8, at_least_1(orders / 10)},
                {"s_inventory", 9, at_least_1(orders / 2)},
                {"delete", 10, 1},
                {"inventory_delete", 11, 1}};
    for (auto& j : jobs) {
      if (!only_table.empty() && only_table != j.name) continue;
      if (j.which >= 10) {
        // delete-date tables: tiny, identical content — child 1 only
        // (cf. reference note in nds_gen_data.py:119-123)
        if (child != 1 && only_table.empty()) continue;
        Writer w(dir + "/" + j.name + suffix);
        gen_delete_table(w, j.which == 10 ? T_DELETE : T_INVENTORY_DELETE,
                         update);
        continue;
      }
      int64_t b, e;
      chunk(j.n, parallel, child, &b, &e);
      if (b >= e && parallel > 1) continue;
      Writer w(dir + "/" + j.name + suffix);
      switch (j.which) {
        case 0: gen_s_purchase(w, update, b, e); break;
        case 1: gen_s_lineitems(w, T_S_PURCHASE_LINEITEM, update, b, e, 3,
                                false, false); break;
        case 2: gen_s_order(w, T_S_CATALOG_ORDER, update, b, e, false); break;
        case 3: gen_s_lineitems(w, T_S_CATALOG_ORDER_LINEITEM, update, b, e,
                                3, true, false); break;
        case 4: gen_s_order(w, T_S_WEB_ORDER, update, b, e, true); break;
        case 5: gen_s_lineitems(w, T_S_WEB_ORDER_LINEITEM, update, b, e, 3,
                                false, true); break;
        case 6: gen_s_returns(w, T_S_STORE_RETURNS, update, b, e, 0); break;
        case 7: gen_s_returns(w, T_S_CATALOG_RETURNS, update, b, e, 1); break;
        case 8: gen_s_returns(w, T_S_WEB_RETURNS, update, b, e, 2); break;
        case 9: gen_s_inventory(w, update, b, e); break;
      }
    }
    return 0;
  }

  static const TableDef tables[] = {
      {"customer_address", gen_customer_address, &Sizes::customer_address},
      {"customer_demographics", gen_customer_demographics,
       &Sizes::customer_demographics},
      {"date_dim", gen_date_dim, &Sizes::date_dim},
      {"warehouse", gen_warehouse, &Sizes::warehouse},
      {"ship_mode", gen_ship_mode, &Sizes::ship_mode},
      {"time_dim", gen_time_dim, &Sizes::time_dim},
      {"reason", gen_reason, &Sizes::reason},
      {"income_band", gen_income_band, &Sizes::income_band},
      {"item", gen_item, &Sizes::item},
      {"store", gen_store, &Sizes::store},
      {"call_center", gen_call_center, &Sizes::call_center},
      {"customer", gen_customer, &Sizes::customer},
      {"web_site", gen_web_site, &Sizes::web_site},
      {"store_returns", gen_store_returns, &Sizes::store_returns},
      {"household_demographics", gen_household_demographics,
       &Sizes::household_demographics},
      {"web_page", gen_web_page, &Sizes::web_page},
      {"promotion", gen_promotion, &Sizes::promotion},
      {"catalog_page", gen_catalog_page, &Sizes::catalog_page},
      {"inventory", gen_inventory, &Sizes::inventory},
      {"catalog_returns", gen_catalog_returns, &Sizes::catalog_returns},
      {"web_returns", gen_web_returns, &Sizes::web_returns},
      {"web_sales", gen_web_sales, &Sizes::web_sales},
      {"catalog_sales", gen_catalog_sales, &Sizes::catalog_sales},
      {"store_sales", gen_store_sales, &Sizes::store_sales},
  };

  for (auto& t : tables) {
    if (!only_table.empty() && only_table != t.name) continue;
    int64_t n = g_sz.*(t.count);
    int64_t b, e;
    chunk(n, parallel, child, &b, &e);
    if (b >= e && parallel > 1) continue;  // empty chunk: no file (dsdgen-like)
    Writer w(dir + "/" + std::string(t.name) + suffix);
    t.gen(w, b, e);
  }
  // dbgen_version: single row, child 1 only
  if ((only_table.empty() && child == 1) || only_table == "dbgen_version") {
    Writer w(dir + "/dbgen_version" + suffix);
    gen_dbgen_version(w, 0, 1);
  }
  return 0;
}
